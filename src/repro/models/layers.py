"""Shared model primitives: norms, RoPE, GQA attention (train / prefill /
decode / seq-sharded long-context decode), MLPs, embeddings.

All functions are pure; parameters are dict pytrees. Weights use bf16,
norm scales fp32, logits/softmax math fp32.

Attention strategy (see DESIGN.md §5 and the spike notes in
EXPERIMENTS.md §Perf):
  * flat-H layout: q-heads sharded on the tp axis; KV heads are expanded
    (repeated) to H locally — legal because KV projections are
    model-replicated whenever kv_heads % tp != 0, and a local gather
    when they are sharded.
  * training uses q-chunked attention via lax.scan (memory-bounded,
    compile-friendly; scores never materialize beyond
    (B, H, chunk, S) fp32).
  * single-token decode attends directly over the cache.
  * long_500k decode uses a shard_map two-pass flash combine over the
    sequence-sharded cache.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.shardings import MeshAxes, constrain

# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    v = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(v + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    v = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(v + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def norm(x, p, cfg: ArchConfig):
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rmsnorm(x, p["scale"], cfg.norm_eps)


def init_norm(cfg: ArchConfig, d: int):
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, D) with D even; positions: (S,) or (B, S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # (S, half)
        ang = ang[None, :, None, :]  # (1, S, 1, half)
    else:
        ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense projections
# ---------------------------------------------------------------------------


def einsum_f32(subscripts, *ops):
    """bf16 inputs, fp32 accumulate/output. On TPU this is a native MXU
    mode (preferred_element_type); the CPU fallback computes the dot in
    bf16 and upcasts the (small) result — upcasting the *operands*
    instead makes XLA-CPU materialize f32 copies of whole KV caches /
    weight stacks inside scan loops, which would poison the dry-run
    byte counts (EXPERIMENTS.md §Dry-run notes)."""
    if jax.default_backend() == "tpu":
        return jnp.einsum(subscripts, *ops, preferred_element_type=jnp.float32)
    return jnp.einsum(subscripts, *ops).astype(jnp.float32)


def dense(x, w, b=None):
    y = x @ w
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def init_dense(rng, d_in, d_out, bias: bool, dtype=jnp.bfloat16):
    scale = 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(rng, (d_in, d_out), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def qkv_proj(x, p, cfg: ArchConfig, ax: MeshAxes, positions):
    b, s, _ = x.shape
    q = dense(x, p["wq"]["w"], p["wq"].get("b")).reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = dense(x, p["wk"]["w"], p["wk"].get("b")).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = dense(x, p["wv"]["w"], p["wv"].get("b")).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if positions is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def expand_kv(k: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """(.., KV, D) -> (.., H, D) repeating each kv head over its q group."""
    g = cfg.num_heads // cfg.num_kv_heads
    if g == 1:
        return k
    return jnp.repeat(k, g, axis=-2)


def fit_chunk(s: int, want: int) -> int:
    """Largest chunk <= want that divides s (trace-time)."""
    c = max(1, min(want, s))
    while s % c:
        c -= 1
    return c


def _causal_window_mask(pos_q, pos_k, window):
    m = pos_q[:, None] >= pos_k[None, :]
    if window is not None:
        m &= pos_q[:, None] - pos_k[None, :] < window
    return m


def attention_core_train(q, k, v, cfg: ArchConfig, ax: MeshAxes, base_pos=0):
    """Chunked causal attention. q, k, v: (B, S, H, D) (kv already
    expanded). Scans over q chunks; scores (B, H, chunk, S) fp32.

    §Perf notes: (1) a flash-style double-chunked online-softmax variant
    was measured and REFUTED at the HLO level — without kernel fusion
    the total score bytes are invariant and the carry adds ~60% traffic
    (EXPERIMENTS.md §Perf, iteration C). (2) the explicit constraint on
    the q-chunk stack below is load-bearing: without it GSPMD shards the
    *chunk* dim over tp and then all-gathers the whole stack every
    iteration (4.3 GB/iter on command-r prefill — iteration D)."""
    b, s, h, d = q.shape
    chunk = fit_chunk(s, cfg.attn_chunk)
    nchunk = s // chunk
    inv = 1.0 / math.sqrt(d)
    pos_k = base_pos + jnp.arange(s)

    qs = q.reshape(b, nchunk, chunk, h, d).transpose(1, 0, 2, 3, 4)
    qs = constrain(qs, P(None, ax.dp, None, ax.tp_if(h), None))

    def body(_, qc_i):
        qc, i = qc_i
        scores = jnp.einsum("bqhd,bthd->bhqt", qc, k).astype(jnp.float32) * inv
        pos_q = base_pos + i * chunk + jnp.arange(chunk)
        mask = _causal_window_mask(pos_q, pos_k, cfg.sliding_window)
        scores = jnp.where(mask[None, None], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        o = jnp.einsum("bhqt,bthd->bqhd", w, v)
        return (), constrain(o, P(ax.dp, None, ax.tp_if(h), None))

    _, outs = jax.lax.scan(body, (), (qs, jnp.arange(nchunk)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h * d)


def attention_train(x, p, cfg: ArchConfig, ax: MeshAxes, positions=None, bidirectional=False):
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = qkv_proj(
        x, p, cfg, ax,
        positions if (cfg.use_rope and cfg.head_dim % 2 == 0) else None,
    )
    k, v = expand_kv(k, cfg), expand_kv(v, cfg)
    q = constrain(q, P(ax.dp, None, ax.tp_if(cfg.num_heads), None))
    k = constrain(k, P(ax.dp, None, ax.tp_if(cfg.num_heads), None))
    v = constrain(v, P(ax.dp, None, ax.tp_if(cfg.num_heads), None))
    if bidirectional:
        cfg2 = dataclasses.replace(cfg, sliding_window=None)
        o = _attention_full_bidir(q, k, v, cfg2)
    else:
        o = attention_core_train(q, k, v, cfg, ax)
    return dense(o, p["wo"]["w"], p["wo"].get("b"))


def _attention_full_bidir(q, k, v, cfg: ArchConfig):
    b, s, h, d = q.shape
    chunk = fit_chunk(s, cfg.attn_chunk)
    nchunk = s // chunk
    inv = 1.0 / math.sqrt(d)
    qs = q.reshape(b, nchunk, chunk, h, d).transpose(1, 0, 2, 3, 4)

    def body(_, qc):
        scores = jnp.einsum("bqhd,bthd->bhqt", qc, k).astype(jnp.float32) * inv
        w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return (), jnp.einsum("bhqt,bthd->bqhd", w, v)

    _, outs = jax.lax.scan(body, (), qs)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h * d)


def cross_attention(x, mem_k, mem_v, p, cfg: ArchConfig, ax: MeshAxes):
    """x: (B, S, D) queries; mem_k/mem_v: (B, T, H, hd) precomputed."""
    b, s, _ = x.shape
    q = dense(x, p["wq"]["w"], p["wq"].get("b")).reshape(b, s, cfg.num_heads, cfg.head_dim)
    inv = 1.0 / math.sqrt(cfg.head_dim)
    scores = jnp.einsum("bqhd,bthd->bhqt", q, mem_k).astype(jnp.float32) * inv
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o = jnp.einsum("bhqt,bthd->bqhd", w, mem_v).reshape(b, s, cfg.q_dim)
    return dense(o, p["wo"]["w"], p["wo"].get("b"))


# -- decode (KV cache) --------------------------------------------------------


def _ring_valid(pos, smax: int, window: int | None):
    """Validity mask + absolute positions for a ring-buffer cache slot.

    Slot i holds absolute position ``pos - ((pos - i) mod smax)`` (the
    most recent write to that slot). Negative -> never written."""
    tpos = jnp.arange(smax)
    abs_pos = pos - jnp.mod(pos - tpos, smax)
    valid = abs_pos >= 0
    if window is not None:
        valid &= (pos - abs_pos) < window
    return valid


def _grouped_attend(q, ck, cv, cfg: ArchConfig, valid, offset_pos=None):
    """Grouped-query attention of one token over a cache shard — the KV
    heads are never expanded/materialized to H (GQA-native einsum).

    q: (B, 1, H, hd); ck/cv: (B, Sloc, KV, hd); valid: (Sloc,) bool.
    Returns fp32 partials (o (B,KV,G,1,hd), m (B,KV,G,1), l (B,KV,G,1))
    so callers can flash-combine across shards."""
    b, _, h, d = q.shape
    kv = ck.shape[2]
    g = h // kv
    qg = q.reshape(b, 1, kv, g, d)
    inv = 1.0 / math.sqrt(d)
    scores = einsum_f32("bqkgd,btkd->bkgqt", qg, ck) * inv
    scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
    m = jnp.max(scores, axis=-1)  # (B, KV, G, 1)
    e = jnp.exp(scores - m[..., None])
    l = jnp.sum(e, axis=-1)
    o = einsum_f32("bkgqt,btkd->bkgqd", e.astype(cv.dtype), cv)
    return o, m, l


def attention_decode_general(x1, cache_k, cache_v, p, cfg: ArchConfig, ax: MeshAxes,
                             pos, plan):
    """One-token decode against a (possibly sharded) KV ring-buffer cache.

    plan (ServePlan) picks the layout: kv-head-sharded / plain (GSPMD
    path) or sequence-sharded (shard_map two-pass flash combine over
    plan.seq_axes, batch sharded over plan.batch_axes)."""
    b = x1.shape[0]
    smax = cache_k.shape[1]
    q, k1, v1 = qkv_proj(x1, p, cfg, ax, None)
    if cfg.use_rope and cfg.head_dim % 2 == 0:
        q = rope(q, jnp.full((1,), pos), cfg.rope_theta)
        k1 = rope(k1, jnp.full((1,), pos), cfg.rope_theta)

    if not plan.seq_axes:
        slot = jnp.asarray(pos % smax, jnp.int32)
        cache_k = jax.lax.dynamic_update_slice(cache_k, k1.astype(cache_k.dtype), (0, slot, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v1.astype(cache_v.dtype), (0, slot, 0, 0))
        bspec = plan.batch_axes or None
        cache_k = constrain(cache_k, P(bspec, None, plan.kv_axes, None))
        cache_v = constrain(cache_v, P(bspec, None, plan.kv_axes, None))
        valid = _ring_valid(pos, smax, cfg.sliding_window)
        o, m, l = _grouped_attend(q, cache_k, cache_v, cfg, valid)
        o = (o / l[..., None]).astype(x1.dtype)
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, 1, cfg.q_dim)
        return dense(o, p["wo"]["w"], p["wo"].get("b")), cache_k, cache_v

    mesh = jax.sharding.get_abstract_mesh()
    seq_axes = plan.seq_axes
    nshard = 1
    for a in seq_axes:
        nshard *= mesh.shape[a]
    sloc = smax // nshard
    bspec = plan.batch_axes or None

    def local(q, k1, v1, ck, cv):
        idx = jnp.zeros((), jnp.int32)
        mul = 1
        for a in reversed(seq_axes):
            idx = idx + jax.lax.axis_index(a) * mul
            mul *= mesh.shape[a]
        offset = idx * sloc
        slot = jnp.asarray(pos % smax, jnp.int32)
        local_slot = jnp.clip(slot - offset, 0, sloc - 1)
        mine = (slot >= offset) & (slot < offset + sloc)
        k1w = jnp.where(mine, 1.0, 0.0).astype(ck.dtype)
        ck = jax.lax.dynamic_update_slice(
            ck,
            k1.astype(ck.dtype) * k1w + jax.lax.dynamic_slice(
                ck, (0, local_slot, 0, 0), k1.shape) * (1 - k1w),
            (0, local_slot, 0, 0),
        )
        cv = jax.lax.dynamic_update_slice(
            cv,
            v1.astype(cv.dtype) * k1w + jax.lax.dynamic_slice(
                cv, (0, local_slot, 0, 0), v1.shape) * (1 - k1w),
            (0, local_slot, 0, 0),
        )
        tpos_abs = pos - jnp.mod(pos - (offset + jnp.arange(sloc)), smax)
        valid = tpos_abs >= 0
        if cfg.sliding_window is not None:
            valid &= (pos - tpos_abs) < cfg.sliding_window
        o_loc, m_loc, l_loc = _grouped_attend(q, ck, cv, cfg, valid)
        m = m_loc
        for a in seq_axes:
            m = jax.lax.pmax(m, a)
        corr = jnp.exp(m_loc - m)
        l = l_loc * corr
        o = o_loc * corr[..., None]
        for a in seq_axes:
            l = jax.lax.psum(l, a)
            o = jax.lax.psum(o, a)
        o = (o / l[..., None]).astype(x1.dtype)  # (B, KV, G, 1, hd)
        o = o.transpose(0, 3, 1, 2, 4).reshape(q.shape[0], 1, cfg.q_dim)
        return o, ck, cv

    qspec = P(bspec, None, None, None)
    seq_spec = P(bspec, seq_axes, None, None)
    o, cache_k, cache_v = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(qspec, qspec, qspec, seq_spec, seq_spec),
        out_specs=(P(bspec, None, None), seq_spec, seq_spec),
        check_vma=False,
    )(q, k1, v1, cache_k, cache_v)
    return dense(o, p["wo"]["w"], p["wo"].get("b")), cache_k, cache_v


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp(x, p, cfg: ArchConfig, ax: MeshAxes):
    if cfg.act == "gelu":  # classic 2-matrix MLP (starcoder2, seamless)
        h = jax.nn.gelu(dense(x, p["wi"]["w"], p["wi"].get("b")))
        h = constrain(h, P(ax.dp, None, ax.tp_if(cfg.d_ff)))
        return dense(h, p["wd"]["w"], p["wd"].get("b"))
    gate_act = jax.nn.gelu if cfg.act == "gelu_gated" else jax.nn.silu
    h = gate_act(dense(x, p["wg"]["w"])) * dense(x, p["wu"]["w"])
    h = constrain(h, P(ax.dp, None, ax.tp_if(cfg.d_ff)))
    return dense(h, p["wd"]["w"], p["wd"].get("b"))


def init_attn(rng, cfg: ArchConfig, dtype=jnp.bfloat16):
    ks = jax.random.split(rng, 4)
    return {
        "wq": init_dense(ks[0], cfg.d_model, cfg.q_dim, cfg.qkv_bias, dtype),
        "wk": init_dense(ks[1], cfg.d_model, cfg.kv_dim, cfg.qkv_bias, dtype),
        "wv": init_dense(ks[2], cfg.d_model, cfg.kv_dim, cfg.qkv_bias, dtype),
        "wo": init_dense(ks[3], cfg.q_dim, cfg.d_model, False, dtype),
    }


def init_mlp(rng, cfg: ArchConfig, d_ff: int | None = None, dtype=jnp.bfloat16):
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    if cfg.act == "gelu":
        return {
            "wi": init_dense(ks[0], cfg.d_model, d_ff, True, dtype),
            "wd": init_dense(ks[1], d_ff, cfg.d_model, True, dtype),
        }
    return {
        "wg": init_dense(ks[0], cfg.d_model, d_ff, False, dtype),
        "wu": init_dense(ks[1], cfg.d_model, d_ff, False, dtype),
        "wd": init_dense(ks[2], d_ff, cfg.d_model, False, dtype),
    }


# ---------------------------------------------------------------------------
# embeddings & loss
# ---------------------------------------------------------------------------


def init_embed(rng, cfg: ArchConfig, dtype=jnp.bfloat16):
    e = jax.random.normal(rng, (cfg.vocab_size, cfg.d_model), jnp.float32)
    return (e * 0.02).astype(dtype)


def embed_tokens(embed, tokens, ax: MeshAxes):
    x = jnp.take(embed, tokens, axis=0)
    return constrain(x, P(ax.dp, None, None))


def unembed(x, embed_or_head, ax: MeshAxes, vocab: int):
    w = embed_or_head
    if w.shape[0] == vocab:  # tied embedding: (V, D) -> project with transpose
        logits = jnp.einsum("bsd,vd->bsv", x, w.astype(x.dtype))
    else:
        logits = x @ w.astype(x.dtype)
    return constrain(logits, P(ax.dp, None, ax.tp_if(vocab)))


def xent_loss(logits, labels, ax: MeshAxes):
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(
        logits.astype(jnp.float32), labels[..., None], axis=-1
    )[..., 0]
    return jnp.mean(lse - ll)
