"""Drivers, one per traffic ``kind``."""
