"""LLaMA-family transformer LM: dense and MoE variants.

Covers the five assigned LM architectures through one config dataclass:
  minicpm-2b     dense, GQA kv=36 (MHA-like), WSD schedule
  llama3.2-1b    dense, GQA kv=8
  qwen3-1.7b     dense, GQA kv=8, qk_norm
  moonshot-v1    MoE 64 experts top-6 (fine-grained, d_ff=1408)
  dbrx-132b      MoE 16 experts top-4

Implementation notes (all driven by the dry-run memory budget):
  * layers run under ``jax.lax.scan`` with per-layer remat
    (``jax.checkpoint``) — compact HLO, activation memory O(1) in depth;
  * attention is **chunked online-softmax** (flash-style in pure JAX):
    queries processed in blocks against the full K/V with running
    (max, sum) statistics — no S×S score materialization, which is what
    lets prefill_32k compile inside 16 GB/chip;
  * decode path takes a KV cache pytree; for ``long_500k`` the cache is
    sequence-sharded over the ``data`` mesh axis (sequence parallelism) and
    the per-step attention is a KV-chunked scan;
  * MoE dispatch is sort-free "dense top-k einsum over capacity buckets":
    tokens are bucketed per expert by cumulative position (deterministic,
    shardable over the expert axis), dropped tokens fall back to residual.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    # MoE (n_experts == 0 → dense)
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # attention
    qk_norm: bool = False
    rope_theta: float = 1e4
    # runtime
    dtype: Any = jnp.bfloat16
    q_chunk: int = 1024
    kv_chunk: int = 2048
    remat: bool = True
    remat_groups: int = 0      # 0 = flat per-layer remat; G>0 = 2-level
    tie_embeddings: bool = False
    # Activation sharding: (batch_axes, seq_axis) mesh-axis names. When set,
    # the residual stream is constrained to P(batch_axes, seq_axis, None) —
    # sequence parallelism between attention blocks — and MoE buckets to
    # P(expert_axis, None, batch_axes). Tuples of strings → hashable.
    act_batch_axes: tuple = ()
    act_seq_axis: Any = None
    moe_expert_axis: Any = None
    # Token-chunked MoE dispatch: bounds the [E, cap, d] bucket working set
    # (and the GSPMD scatter-fallback payloads) to one chunk at a time.
    moe_chunk: int = 65536

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads

    @property
    def vocab_padded(self) -> int:
        """Vocab rounded up to 256 so embedding tables shard over any mesh
        axis (standard practice; padded classes are ordinary softmax slots
        that targets never index)."""
        return -(-self.vocab // 256) * 256

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    def param_count(self) -> int:
        """Total parameters (embeddings counted once if tied)."""
        d, f = self.d_model, self.d_ff
        attn = d * (self.n_heads * self.d_head) + 2 * d * (self.n_kv_heads * self.d_head) \
            + (self.n_heads * self.d_head) * d
        if self.is_moe:
            mlp = 3 * d * f * self.n_experts
        else:
            mlp = 3 * d * f
        per_layer = attn + mlp + 2 * d
        emb = self.vocab * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + d

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: top_k experts only)."""
        if not self.is_moe:
            return self.param_count()
        d, f = self.d_model, self.d_ff
        dense_mlp = 3 * d * f * self.n_experts
        active_mlp = 3 * d * f * self.top_k
        return self.param_count() - self.n_layers * (dense_mlp - active_mlp)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def init_params(cfg: LMConfig, key: jax.Array) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    hq = cfg.n_heads * cfg.d_head
    hkv = cfg.n_kv_heads * cfg.d_head
    k_emb, k_layers, k_out = jax.random.split(key, 3)

    def norm_init(shape, key, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(cfg.dtype)

    ks = jax.random.split(k_layers, 10)
    L = cfg.n_layers

    def stack(shape, key, fan_in):
        return norm_init((L,) + shape, key, fan_in)

    layer = {
        "wq": stack((d, hq), ks[0], d),
        "wk": stack((d, hkv), ks[1], d),
        "wv": stack((d, hkv), ks[2], d),
        "wo": stack((hq, d), ks[3], hq),
        "ln_attn": jnp.ones((L, d), jnp.float32),
        "ln_mlp": jnp.ones((L, d), jnp.float32),
    }
    if cfg.qk_norm:
        layer["q_norm"] = jnp.ones((L, cfg.d_head), jnp.float32)
        layer["k_norm"] = jnp.ones((L, cfg.d_head), jnp.float32)
    if cfg.is_moe:
        layer["router"] = norm_init((L, d, cfg.n_experts), ks[4], d)
        layer["w_gate"] = stack((cfg.n_experts, d, f), ks[5], d)
        layer["w_up"] = stack((cfg.n_experts, d, f), ks[6], d)
        layer["w_down"] = stack((cfg.n_experts, f, d), ks[7], f)
    else:
        layer["w_gate"] = stack((d, f), ks[5], d)
        layer["w_up"] = stack((d, f), ks[6], d)
        layer["w_down"] = stack((f, d), ks[7], f)

    params = {
        "embed": norm_init((cfg.vocab_padded, d), k_emb, d),
        "layers": layer,
        "ln_out": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = norm_init((d, cfg.vocab_padded), k_out, d)
    return params


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def _constrain_act(cfg: "LMConfig", x: jnp.ndarray) -> jnp.ndarray:
    """Sequence-parallel residual stream: P(batch_axes, seq_axis, None)."""
    if not cfg.act_batch_axes and cfg.act_seq_axis is None:
        return x
    from jax.sharding import PartitionSpec as P
    spec = P(cfg.act_batch_axes or None, cfg.act_seq_axis, None)
    return jax.lax.with_sharding_constraint(x, spec)


def _constrain_moe_buckets(cfg: "LMConfig", b: jnp.ndarray) -> jnp.ndarray:
    """Buckets [E, cap, d]: experts over the model axis (EP), capacity over
    the data axes (all-to-all dispatch), d replicated. The FSDP-stored
    expert weights are all-gathered per layer (``_gather_moe_weight``) so
    the expert einsum contracts shard-local — gathering ~400 MB of weights
    beats psum-ing multi-GB cap×d_ff partials by ~300×."""
    if cfg.moe_expert_axis is None:
        return b
    from jax.sharding import PartitionSpec as P
    spec = P(cfg.moe_expert_axis, None, cfg.act_batch_axes or None)
    return jax.lax.with_sharding_constraint(b, spec)


def _gather_moe_weight(cfg: "LMConfig", w: jnp.ndarray) -> jnp.ndarray:
    """FSDP un-shard: [E, d, f] weight → experts sharded, d/f gathered."""
    if cfg.moe_expert_axis is None:
        return w
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        w, P(cfg.moe_expert_axis, None, None))


def _constrain_moe_tokens(cfg: "LMConfig", x: jnp.ndarray) -> jnp.ndarray:
    """Flat token table [T, d]: T = batch×seq merges the batch axes with
    the sequence-parallel axis."""
    if not cfg.act_batch_axes and cfg.act_seq_axis is None:
        return x
    from jax.sharding import PartitionSpec as P
    axes = tuple(cfg.act_batch_axes)
    if cfg.act_seq_axis is not None:
        axes = axes + (cfg.act_seq_axis,)
    return jax.lax.with_sharding_constraint(x, P(axes or None, None))


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale * w).astype(x.dtype)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., S, H, Dh]; positions: [..., S]."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def chunked_attention(q, k, v, *, causal: bool, q_offset, q_chunk: int,
                      kv_chunk: int) -> jnp.ndarray:
    """Flash-style online-softmax attention in pure JAX.

    q: [B, Sq, Hq, Dh]; k, v: [B, Skv, Hkv, Dh]; GQA by head replication
    factor Hq // Hkv. Never materializes Sq × Skv scores: scans KV chunks
    with running (max, sum, acc) statistics, queries processed in blocks.
    """
    b, sq, hq, dh = q.shape
    _, skv, hkv, _ = k.shape
    rep = hq // hkv
    scale = dh ** -0.5
    n_q = max(sq // q_chunk, 1)
    qc = sq // n_q
    n_kv = max(skv // kv_chunk, 1)
    kvc = skv // n_kv

    q = q.reshape(b, n_q, qc, hq, dh)
    k = k.reshape(b, n_kv, kvc, hkv, dh)
    v = v.reshape(b, n_kv, kvc, hkv, dh)

    # vmap over batch; KV chunks scanned with online-softmax statistics.
    def per_batch(qb, kb, vb):
        def scan_body(_, qi):
            def kv_step(carry, kj):
                m, l, acc = carry
                k_blk = kb[kj]
                v_blk = vb[kj]
                k_pos = kj * kvc + jnp.arange(kvc)
                krep = jnp.repeat(k_blk, rep, axis=1)
                vrep = jnp.repeat(v_blk, rep, axis=1)
                q_blk = qb[qi]
                q_pos = q_offset + qi * qc + jnp.arange(qc)
                s = jnp.einsum("qhd,khd->hqk", q_blk, krep).astype(jnp.float32) * scale
                if causal:
                    mask = q_pos[:, None] >= k_pos[None, :]
                    s = jnp.where(mask[None], s, -1e30)
                m_new = jnp.maximum(m, s.max(axis=-1))
                p = jnp.exp(s - m_new[..., None])
                alpha = jnp.exp(m - m_new)
                l_new = l * alpha + p.sum(axis=-1)
                acc_new = acc * alpha[..., None] + jnp.einsum(
                    "hqk,khd->hqd", p.astype(vrep.dtype), vrep).astype(jnp.float32)
                return (m_new, l_new, acc_new), None

            m0 = jnp.full((hq, qc), -1e30, jnp.float32)
            l0 = jnp.zeros((hq, qc), jnp.float32)
            acc0 = jnp.zeros((hq, qc, dh), jnp.float32)
            (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, acc0),
                                          jnp.arange(n_kv))
            out = acc / jnp.maximum(l[..., None], 1e-30)
            return None, out.transpose(1, 0, 2)

        _, outs = jax.lax.scan(scan_body, None, jnp.arange(n_q))
        return outs.reshape(sq, hq, dh)

    out = jax.vmap(per_batch)(q, k, v)
    return out.astype(q.dtype)


import contextvars

_LM_MESH: contextvars.ContextVar = contextvars.ContextVar("lm_mesh",
                                                          default=None)


def set_lm_mesh(mesh) -> None:
    """Mesh handle for the shard_map MoE path (set by the step factory)."""
    _LM_MESH.set(mesh)


def moe_block_shard_map(x, router_w, w_gate, w_up, w_down, cfg: LMConfig,
                        mesh):
    """Expert-parallel MoE via shard_map (beyond-paper §Perf P1-i7).

    GSPMD auto-partitioning of the scatter/gather dispatch falls back to
    replicate+all-reduce (measured: 40× einsum overcompute — every chip
    ran the FULL per-expert capacity — and ~2 TB/chip of fallback
    all-reduce on dbrx train_4k). This path expresses the parallelism
    explicitly instead:

      * tokens stay sharded over the data axes; the (sequence-parallel)
        model-axis shard of the residual is all-gathered once per layer;
      * each model rank routes all of its data-shard's tokens but buckets
        ONLY the experts it owns (E / |model| each) — dispatch is a purely
        LOCAL scatter, so no GSPMD fallback exists by construction;
      * expert weights are FSDP-stored (d over data) and all-gathered
        shard-locally before the GEMM (~400 MB/layer);
      * each rank's partial output (its experts' contributions) is
        combined with one reduce-scatter over the model axis — restoring
        the sequence-parallel layout for the next block.

    Per-chip per-layer collective: all-gather + reduce-scatter of one
    residual slice + 3 weight gathers — versus the fallback's multi-GB
    all-reduces per scatter/gather pair.
    """
    from jax.sharding import PartitionSpec as P

    da = tuple(cfg.act_batch_axes)
    seq_ax = cfg.act_seq_axis
    model_ax = cfg.moe_expert_axis
    e_total, k = cfg.n_experts, cfg.top_k
    model_size = mesh.shape[model_ax]
    e_per = e_total // model_size
    assert e_total % model_size == 0

    def local_fn(x_l, rw, wg_l, wu_l, wd_l):
        # x_l: [b_l, s_l, d]; w*_l: expert shard with d split over 'data'.
        wg = jax.lax.all_gather(wg_l, "data", axis=1, tiled=True)
        wu = jax.lax.all_gather(wu_l, "data", axis=1, tiled=True)
        wd = jax.lax.all_gather(wd_l, "data", axis=2, tiled=True)
        if seq_ax is not None:
            x_full = jax.lax.all_gather(x_l, seq_ax, axis=1, tiled=True)
        else:
            x_full = x_l
        bl, s, d = x_full.shape
        t = bl * s
        xf = x_full.reshape(t, d)

        logits = xf.astype(jnp.float32) @ rw.astype(jnp.float32)
        gates = jax.nn.softmax(logits, axis=-1)
        top_g, top_e = jax.lax.top_k(gates, k)
        top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)

        # This rank owns experts [ridx·e_per, (ridx+1)·e_per).
        ridx = jax.lax.axis_index(model_ax)
        local_e = top_e - ridx * e_per
        mine = (local_e >= 0) & (local_e < e_per)
        le_safe = jnp.where(mine, local_e, 0)

        cap = int(max(1, round(t * k / e_total * cfg.capacity_factor)))
        cap = -(-cap // 8) * 8
        onehot = (jax.nn.one_hot(le_safe, e_per, dtype=jnp.int32)
                  * mine[..., None])
        flat = onehot.reshape(t * k, e_per)
        pos = jnp.sum(flat * (jnp.cumsum(flat, axis=0) - flat),
                      axis=-1).reshape(t, k)
        keep = mine & (pos < cap)

        e_idx = jnp.where(keep, le_safe, e_per)          # e_per → dropped
        p_idx = jnp.where(keep, pos, 0)
        buckets = jnp.zeros((e_per, cap, d), xf.dtype)
        for j in range(k):                               # LOCAL scatter
            buckets = buckets.at[e_idx[:, j], p_idx[:, j]].add(
                xf, mode="drop")

        g = jnp.einsum("ecd,edf->ecf", buckets, wg)
        u = jnp.einsum("ecd,edf->ecf", buckets, wu)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xf.dtype) * u
        y = jnp.einsum("ecf,efd->ecd", h, wd)            # [e_per, cap, d]

        out = jnp.zeros_like(xf)
        for j in range(k):                               # LOCAL combine
            yj = y[e_idx[:, j], p_idx[:, j]]
            yj = jnp.where(keep[:, j:j + 1], yj, 0)
            out = out + yj * top_g[:, j:j + 1].astype(xf.dtype)
        out = out.reshape(bl, s, d)
        if seq_ax is not None:
            return jax.lax.psum_scatter(out, seq_ax, scatter_dimension=1,
                                        tiled=True)
        return jax.lax.psum(out, model_ax)

    x_spec = P(da or None, seq_ax, None)
    return jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None),
                  P(model_ax, "data", None), P(model_ax, "data", None),
                  P(model_ax, None, "data")),
        out_specs=x_spec,
        check_vma=False,
    )(x, router_w, w_gate, w_up, w_down)


def moe_block(x, router_w, w_gate, w_up, w_down, cfg: LMConfig):
    """Token-chunked capacity-bucketed top-k MoE (deterministic).

    x: [B, S, d]. Returns [B, S, d]. Tokens beyond an expert's per-chunk
    capacity are dropped (standard Switch behavior). Chunking bounds the
    bucket working set: at dbrx train scale the unchunked [E, cap, d]
    dispatch buffers (plus their GSPMD scatter fallbacks) peak >100 GiB.

    Chunks split the (data-sharded) batch dim into contiguous per-shard
    blocks × chunk index — i.e. ``[bc, n_ch, S, d]`` — so every chunk
    carries one batch row per data shard and the full (model-sharded)
    sequence: perfectly load-balanced, zero resharding.
    """
    b, s, d = x.shape
    rows_per_chunk = max(1, cfg.moe_chunk // s)
    n_ch = b // rows_per_chunk if rows_per_chunk else 1
    if n_ch > 1 and b % n_ch == 0:
        bc = b // n_ch
        view = x.reshape(bc, n_ch, s, d)
        xs = jnp.moveaxis(view, 1, 0)                  # [n_ch, bc, S, d]

        @jax.checkpoint
        def one_chunk(x_blk):
            flat = _constrain_moe_tokens(cfg, x_blk.reshape(bc * s, d))
            y = _moe_block_flat(flat, router_w, w_gate, w_up, w_down, cfg)
            return y.reshape(bc, s, d)

        ys = jax.lax.map(one_chunk, xs)
        return jnp.moveaxis(ys, 0, 1).reshape(b, s, d)
    flat = _moe_block_flat(x.reshape(b * s, d), router_w, w_gate, w_up,
                           w_down, cfg)
    return flat.reshape(b, s, d)


def _moe_block_flat(x, router_w, w_gate, w_up, w_down, cfg: LMConfig):
    t, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    cap = int(max(1, round(t * k / e * cfg.capacity_factor)))
    # Keep MXU dims aligned.
    cap = -(-cap // 8) * 8

    x = _constrain_moe_tokens(cfg, x)
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)             # [T, E]
    top_g, top_e = jax.lax.top_k(gates, k)              # [T, k]
    top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)

    # Position of each (token, slot) within its expert's bucket.
    onehot = jax.nn.one_hot(top_e, e, dtype=jnp.int32)   # [T, k, E]
    flat = onehot.reshape(t * k, e)
    pos_in_e = jnp.cumsum(flat, axis=0) - flat           # [T·k, E]
    pos = jnp.sum(flat * pos_in_e, axis=-1).reshape(t, k)
    keep = pos < cap

    # Scatter tokens into [E, cap, d] buckets — one scatter per top-k slot
    # so no [T·k, d] staging copy of x ever materializes (at dbrx scale
    # that buffer is 96 GiB/chip).
    e_idx = jnp.where(keep, top_e, e)                    # e → dropped
    p_idx = jnp.where(keep, pos, 0)
    buckets = jnp.zeros((e, cap, d), x.dtype)
    for j in range(k):
        buckets = buckets.at[e_idx[:, j], p_idx[:, j]].add(x, mode="drop")
    buckets = _constrain_moe_buckets(cfg, buckets)

    # Expert FFN on buckets (einsum over the expert axis → EP-shardable;
    # weights FSDP-gathered to shard-local-full d/f first).
    w_gate = _gather_moe_weight(cfg, w_gate)
    w_up = _gather_moe_weight(cfg, w_up)
    w_down = _gather_moe_weight(cfg, w_down)
    g = jnp.einsum("ecd,edf->ecf", buckets, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buckets, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("ecf,efd->ecd", h, w_down)            # [E, cap, d]
    y = _constrain_moe_buckets(cfg, y)

    # Combine: per-slot gather + gate-weighted accumulate (again no T·k
    # staging buffer).
    out = jnp.zeros_like(x)
    for j in range(k):
        yj = y[e_idx[:, j], p_idx[:, j]]                 # [T, d]
        yj = jnp.where(keep[:, j:j + 1], yj, 0)
        out = out + _constrain_moe_tokens(
            cfg, yj * top_g[:, j:j + 1].astype(x.dtype))
    return out


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _layer_fwd(cfg: LMConfig, x, lp, positions, kv_cache=None):
    """One transformer layer. x: [B, S, d]. Returns (x, new_kv)."""
    b, s, d = x.shape
    h = rms_norm(x, lp["ln_attn"])
    q = (h @ lp["wq"]).reshape(b, s, cfg.n_heads, cfg.d_head)
    k = (h @ lp["wk"]).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = (h @ lp["wv"]).reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    if cfg.qk_norm:
        q = rms_norm(q, lp["q_norm"])
        k = rms_norm(k, lp["k_norm"])
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    if kv_cache is None:
        attn = chunked_attention(q, k, v, causal=True, q_offset=0,
                                 q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk)
        new_kv = None
    else:
        ck, cv = kv_cache                                # [B, Skv, Hkv, Dh]
        # Decode: append is the caller's job (functional update outside);
        # here the cache already contains the new token's K/V.
        attn = chunked_attention(q, ck, cv, causal=False, q_offset=0,
                                 q_chunk=1, kv_chunk=cfg.kv_chunk)
        new_kv = (k, v)

    x = x + (attn.reshape(b, s, -1) @ lp["wo"])
    h2 = rms_norm(x, lp["ln_mlp"])
    if cfg.is_moe:
        mesh = _LM_MESH.get()
        if mesh is not None and cfg.moe_expert_axis is not None:
            y = moe_block_shard_map(h2, lp["router"], lp["w_gate"],
                                    lp["w_up"], lp["w_down"], cfg, mesh)
        else:
            y = moe_block(h2, lp["router"], lp["w_gate"],
                          lp["w_up"], lp["w_down"], cfg)
    else:
        g = jax.nn.silu((h2 @ lp["w_gate"]).astype(jnp.float32)).astype(x.dtype)
        y = (g * (h2 @ lp["w_up"])) @ lp["w_down"]
    return (x + y).astype(cfg.dtype), new_kv


def forward_hidden(cfg: LMConfig, params, tokens):
    """Backbone forward → post-ln hidden states [B, S, d].

    Layer stack runs under ``lax.scan``; with ``remat_groups = G > 0`` the
    scan is two-level (G outer groups × L/G inner layers, both
    checkpointed) which cuts the residual stash from L to G + L/G slices —
    the classic √L memory trade for one extra forward recompute.
    """
    b, s = tokens.shape
    x = params["embed"][tokens].astype(cfg.dtype)
    x = _constrain_act(cfg, x)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def one_layer(x, lp):
        fn = lambda x_, lp_: _constrain_act(
            cfg, _layer_fwd(cfg, x_, lp_, positions)[0])
        if cfg.remat:
            fn = jax.checkpoint(fn)
        return fn(x, lp), None

    g = cfg.remat_groups
    if g and cfg.n_layers % g == 0:
        per = cfg.n_layers // g
        grouped = jax.tree.map(
            lambda a: a.reshape((g, per) + a.shape[1:]), params["layers"])

        @jax.checkpoint
        def group(x, gp):
            x, _ = jax.lax.scan(one_layer, x, gp)
            return x

        def outer(x, gp):
            return group(x, gp), None

        x, _ = jax.lax.scan(outer, x, grouped)
    else:
        x, _ = jax.lax.scan(one_layer, x, params["layers"])
    return rms_norm(x, params["ln_out"])


def _unembed(cfg: LMConfig, params):
    unemb = params.get("unembed")
    if unemb is None:
        unemb = params["embed"].T.astype(cfg.dtype)
    return unemb


def forward(cfg: LMConfig, params, tokens):
    """Training/prefill forward → logits [B, S, vocab]."""
    return forward_hidden(cfg, params, tokens) @ _unembed(cfg, params)


def lm_loss(cfg: LMConfig, params, tokens, targets, *, loss_chunk: int = 512):
    """Cross-entropy with SEQ-CHUNKED logits.

    fp32 logits for train_4k are B·S·V ≈ 0.5 TB global — materializing them
    (plus the softmax cotangent) blows the 16 GB/chip HBM budget. Chunking
    the unembed+logsumexp over sequence blocks under ``jax.checkpoint``
    keeps peak logits memory at B·chunk·V/chips and recomputes them in the
    backward pass (one extra unembed matmul — ~3% of step FLOPs).
    """
    x = forward_hidden(cfg, params, tokens)            # [B, S, d]
    unemb = _unembed(cfg, params)
    b, s, d = x.shape
    n_chunks = max(s // loss_chunk, 1)
    c = s // n_chunks
    xc = x.reshape(b, n_chunks, c, d).swapaxes(0, 1)   # [n, B, c, d]
    tc = targets.reshape(b, n_chunks, c).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_loss(x_blk, t_blk):
        logits = (x_blk @ unemb).astype(jnp.float32)   # [B, c, V]
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t_blk[..., None], axis=-1)[..., 0]
        return jnp.sum(logz - gold)

    def body(acc, xs):
        x_blk, t_blk = xs
        return acc + chunk_loss(x_blk, t_blk), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, tc))
    return total / (b * s)


# ---------------------------------------------------------------------------
# Decode (serving)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: LMConfig, batch: int, seq_len: int):
    shape = (cfg.n_layers, batch, seq_len, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype),
            "len": jnp.zeros((), jnp.int32)}


def decode_step(cfg: LMConfig, params, token, cache):
    """One decode step. token: [B] int32 → (logits [B, vocab], new cache).

    Attention runs against the *full static cache length* with masking by
    ``cache['len']`` folded into the KV values being zero-initialized and a
    mask on positions ≥ len. The cache has static shape [L, B, S, Hkv, Dh]
    (sequence-shardable over the data axis for long_500k).
    """
    b = token.shape[0]
    x = params["embed"][token][:, None, :].astype(cfg.dtype)   # [B, 1, d]
    pos = jnp.broadcast_to(cache["len"], (b, 1))
    s_max = cache["k"].shape[2]

    def body(carry, inputs):
        x, = carry
        lp, ck, cv = inputs
        h = rms_norm(x, lp["ln_attn"])
        q = (h @ lp["wq"]).reshape(b, 1, cfg.n_heads, cfg.d_head)
        k = (h @ lp["wk"]).reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
        v = (h @ lp["wv"]).reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
        if cfg.qk_norm:
            q = rms_norm(q, lp["q_norm"])
            k = rms_norm(k, lp["k_norm"])
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k, cache["len"], axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v, cache["len"], axis=1)

        # Masked decode attention over the static-length cache.
        rep = cfg.n_heads // cfg.n_kv_heads
        krep = jnp.repeat(ck, rep, axis=2)               # [B, S, Hq, Dh]
        vrep = jnp.repeat(cv, rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, krep).astype(jnp.float32)
        s = s * (cfg.d_head ** -0.5)
        kpos = jnp.arange(s_max)
        s = jnp.where((kpos <= cache["len"])[None, None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vrep.dtype), vrep)

        x = x + (attn.reshape(b, 1, -1) @ lp["wo"])
        h2 = rms_norm(x, lp["ln_mlp"])
        if cfg.is_moe:
            y = moe_block(h2, lp["router"], lp["w_gate"],
                          lp["w_up"], lp["w_down"], cfg)
        else:
            g = jax.nn.silu((h2 @ lp["w_gate"]).astype(jnp.float32)).astype(x.dtype)
            y = (g * (h2 @ lp["w_up"])) @ lp["w_down"]
        return (x + y,), (ck, cv)

    (x,), (new_k, new_v) = jax.lax.scan(
        body, (x,), (params["layers"], cache["k"], cache["v"]))
    x = rms_norm(x, params["ln_out"])
    unemb = params.get("unembed")
    if unemb is None:
        unemb = params["embed"].T.astype(cfg.dtype)
    logits = (x @ unemb)[:, 0, :cfg.vocab]
    new_cache = {"k": new_k, "v": new_v, "len": cache["len"] + 1}
    return logits.astype(jnp.float32), new_cache
