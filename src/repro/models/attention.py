"""Attention: MHA/GQA/MQA with RoPE, causal/sliding-window masks, softcap,
KV caches (full or ring-buffer for local layers), cross-attention.

Projections are stored *flattened* — wq: (D, H·Dh), wk/wv: (D, KV·Dh),
wo: (H·Dh, D) — so the tensor-parallel shard axis is the fused head dim,
which is divisible by the 16-way ``model`` axis for every assigned arch
(raw head counts like 36 or 10 are not).  Heads are reshaped locally.

Two execution paths:
  * ``einsum`` — reference XLA path (smoke tests AND the dry-run, so
    ``cost_analysis`` sees explicit FLOPs/bytes);
  * ``pallas`` — TPU flash kernels from ``repro.kernels`` (tiled, O(S)
    memory), validated against this path in interpret mode.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from functools import partial

from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..sharding import current_mesh, logical_to_pspec, mesh_axis, shard
from .layers import apply_rope
from .params import ParamStore


def shard_seq(x: jax.Array, dim: int = 1) -> jax.Array:
    """Sequence-shard an activation over the model axis when divisible.

    Keeps the huge RoPE / attention intermediates distributed: without this,
    (B,S,H,Dh) f32 temporaries replicate over the 16-way model axis (head
    counts like 36/10/8 are not divisible by it; the sequence always is)."""
    _, size = mesh_axis("q_seq")
    if size > 1 and x.shape[dim] % size == 0 and x.shape[dim] > 1:
        axes = [None] * x.ndim
        axes[0] = "batch"
        axes[dim] = "q_seq"
        return shard(x, *axes)
    return x


def init_attention(ps: ParamStore, path: str, cfg: ModelConfig,
                   stacked: Optional[int]):
    D, H, KV, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pre = (stacked,) if stacked else ()
    pax = (None,) if stacked else ()
    ps.param(f"{path}/wq", pre + (D, H * Dh), pax + ("fsdp", "model"), "fan_in")
    ps.param(f"{path}/wk", pre + (D, KV * Dh), pax + ("fsdp", "model"), "fan_in")
    ps.param(f"{path}/wv", pre + (D, KV * Dh), pax + ("fsdp", "model"), "fan_in")
    ps.param(f"{path}/wo", pre + (H * Dh, D), pax + ("model", "fsdp"), "fan_in")


def _proj(x: jax.Array, w: jax.Array, heads: int, head_dim: int) -> jax.Array:
    y = jnp.einsum("bsd,dm->bsm", x, w.astype(x.dtype))
    y = shard(y, "batch", None, "model")
    return y.reshape(*y.shape[:-1], heads, head_dim)


def _unproj(y: jax.Array, w: jax.Array, dtype) -> jax.Array:
    yf = y.reshape(*y.shape[:-2], -1)
    yf = shard(yf, "batch", None, "model")
    return jnp.einsum("bsm,md->bsd", yf, w.astype(dtype))


def _attend_einsum(q, k, v, mask, softcap, scale):
    """Grouped-query attention without materialising repeated KV.

    q: (B,Sq,H,Dh); k,v: (B,Sk,KV,Dh); H = KV·groups.
    mask: (1|B, 1, Sq, Sk) or None.  Returns (B,Sq,H,Dh).
    """
    B, Sq, H, Dh = q.shape
    KV = k.shape[2]
    g = H // KV
    qg = q.reshape(B, Sq, KV, g, Dh)
    logits = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) * scale
    logits = logits.astype(jnp.float32)
    if softcap:
        logits = jnp.tanh(logits / softcap) * softcap
    if mask is not None:
        logits = jnp.where(mask[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, v)
    return out.reshape(B, Sq, H, Dh)


def _attend_blocked(q, k, v, *, causal: bool, window: Optional[int],
                    softcap: Optional[float], scale: float,
                    chunk: int = 1024, scores_f32: bool = True):
    """Flash-style blocked attention in pure XLA.

    Why this exists (measured, see EXPERIMENTS.md §Perf iteration 1):
    * the naive einsum path materialises (Sq × Sk) logits — 159 GB/device on
      starcoder2-7b prefill_32k;
    * GQA head counts (36, 10, 8...) don't divide the 16-way ``model`` axis,
      so XLA replicates attention over it.  Here each q chunk is sharded on
      its SEQUENCE dim over ``model`` (context parallelism): divisible for
      every arch, balanced for causal masks (all shards share the k range).
    * causal/window chunks slice exactly the valid k range — no online
      softmax needed, out-of-window blocks never computed;
    * each chunk is wrapped in ``jax.checkpoint`` so backward recomputes it
      instead of saving per-chunk probabilities (Σ chunks = full S² again).
    """
    B, Sq, H, Dh = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    g = H // KV
    qg = q.reshape(B, Sq, KV, g, Dh)
    chunk = min(chunk, Sq)
    outs = []
    for i0 in range(0, Sq, chunk):
        i1 = min(i0 + chunk, Sq)
        k_hi = min(i1, Sk) if causal else Sk
        k_lo = 0
        if window is not None:
            k_lo = max(0, ((i0 - window + 1) // 128) * 128)

        def do_chunk(qs, ks, vs, i0=i0, i1=i1, k_lo=k_lo, k_hi=k_hi):
            s = jnp.einsum("bqkgd,bskd->bkgqs", qs, ks) * scale
            s = s.astype(jnp.float32 if scores_f32 else qs.dtype)
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            rows = i0 + jnp.arange(i1 - i0)[:, None]
            cols = k_lo + jnp.arange(k_hi - k_lo)[None, :]
            m = jnp.ones((i1 - i0, k_hi - k_lo), bool)
            if causal:
                m &= cols <= rows
            if window is not None:
                m &= cols > rows - window
            s = jnp.where(m[None, None, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(qs.dtype)
            return jnp.einsum("bkgqs,bskd->bqkgd", p, vs)

        qs = shard(qg[:, i0:i1], "batch", "q_seq", None, None, None)
        o = jax.checkpoint(do_chunk)(qs, k[:, k_lo:k_hi], v[:, k_lo:k_hi])
        outs.append(shard(o, "batch", "q_seq", None, None, None))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    return out.reshape(B, Sq, H, Dh)


def _in_manual_region() -> bool:
    """True inside a partial-manual shard_map (e.g. pipeline 'pod' stages)."""
    am = jax.sharding.get_abstract_mesh()
    return am is not None and bool(am.shape) and any(
        t == jax.sharding.AxisType.Manual for t in am.axis_types)


def _attend_cp(q, k, v, *, causal: bool, softcap: Optional[float],
               scale: float, chunk: int = 512, unroll: bool = False,
               scores_f32: bool = True):
    """Context-parallel attention via ``shard_map`` (global/unbounded layers).

    q is sequence-sharded over the model axis; each device holds S/16 query
    rows and streams them in serial chunks against the full K/V (explicit
    all-gather at the shard_map boundary — it shows up in the collective
    roofline term, ~2·S·KV·Dh bytes/layer).  Per-chunk working set is
    (B_loc · H · chunk · S) f32 — the serial python loop bounds live memory,
    which one fused einsum over all local rows would not.

    Causal masking is applied against full K (no early-exit): ~2× the
    minimal causal FLOPs, same as any masked-dense formulation; the Pallas
    kernel path removes that factor on real TPUs.
    """
    mesh = current_mesh()
    seq_axes, n_seq = mesh_axis("q_seq")
    B, Sq, H, Dh = q.shape
    if mesh is None or n_seq <= 1 or Sq % n_seq or Sq == 1 \
            or _in_manual_region():
        # _in_manual_region: nested manual computations over different axes
        # are not supported (pipeline stages bind 'pod'); use plain blocks
        return _attend_blocked(q, k, v, causal=causal, window=None,
                               softcap=softcap, scale=scale, chunk=chunk,
                               scores_f32=scores_f32)
    Sk, KV = k.shape[1], k.shape[2]
    g = H // KV
    S_loc = Sq // n_seq
    seq_ax = seq_axes[0]
    # bound one chunk's f32 score tensor to ~1 GB of live memory per device
    # (several layout copies of it coexist in the fused HLO)
    _, n_batch = mesh_axis("batch")
    b_loc = max(1, B // max(n_batch, 1))
    budget = int(1e9)
    max_chunk = max(64, budget // max(b_loc * H * Sk * 4, 1))
    chunk = min(chunk, 1 << (max_chunk.bit_length() - 1))
    bspec = logical_to_pspec(["batch"])         # batch mesh axes
    bax = bspec[0] if len(bspec) else None

    chunk = min(chunk, S_loc)
    while S_loc % chunk:
        chunk //= 2
    nc = S_loc // chunk

    def body(q_loc, k_f, v_f):
        midx = jax.lax.axis_index(seq_ax)
        row0 = midx * S_loc
        bl = q_loc.shape[0]
        qg = q_loc.reshape(bl, S_loc, KV, g, Dh)

        def do_chunk(qs, rows):
            s = jnp.einsum("bqkgd,bskd->bkgqs", qs, k_f) * scale
            s = s.astype(jnp.float32 if scores_f32 else qs.dtype)
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            if causal:
                cols = jnp.arange(Sk)[None, :]
                s = jnp.where(cols <= rows[:, None], s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(qs.dtype)
            return jnp.einsum("bkgqs,bskd->bqkgd", p, v_f)

        if unroll:
            # probe path: unrolled chunks => cost_analysis counts every one
            outs = []
            for ci in range(nc):
                rows = row0 + ci * chunk + jnp.arange(chunk)
                outs.append(jax.checkpoint(do_chunk)(
                    qg[:, ci * chunk:(ci + 1) * chunk], rows))
            out = outs[0] if nc == 1 else jnp.concatenate(outs, axis=1)
        else:
            # production path: lax.scan serialises chunks — one chunk's f32
            # scores live at a time (the unrolled form peaked at ~40 GB on
            # starcoder2 prefill_32k: XLA:CPU keeps all chunk buffers live)
            xs = qg.reshape(bl, nc, chunk, KV, g, Dh).transpose(
                1, 0, 2, 3, 4, 5)

            def sbody(_, inp):
                qs, ci = inp
                rows = row0 + ci * chunk + jnp.arange(chunk)
                return None, jax.checkpoint(do_chunk)(qs, rows)

            _, os_ = jax.lax.scan(sbody, None,
                                  (xs, jnp.arange(nc, dtype=jnp.int32)))
            out = os_.transpose(1, 0, 2, 3, 4, 5).reshape(
                bl, S_loc, KV, g, Dh)
        return out.reshape(bl, S_loc, H, Dh)

    # manual ONLY over the sequence axis: batch/model stay auto, so this
    # composes under an outer (pipeline) shard_map that has 'pod' manual
    mesh_arg = (jax.sharding.get_abstract_mesh() if _in_manual_region()
                else mesh)
    fn = shard_map(body, mesh=mesh_arg,
                   in_specs=(P(None, seq_ax, None, None),
                             P(None, None, None, None),
                             P(None, None, None, None)),
                   out_specs=P(None, seq_ax, None, None),
                   axis_names={seq_ax}, check_vma=False)
    return fn(q, k, v)


def make_causal_mask(sq: int, sk: int, q_offset, window: Optional[int]):
    """(1,1,Sq,Sk) bool; window=None => full causal, else sliding window."""
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    return m[None, None]


def self_attention(p, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                   window: Optional[int], causal: bool = True,
                   return_kv: bool = False):
    """Training/prefill self-attention over the whole (possibly windowed) seq."""
    B, S, D = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    groups = H // KV
    q = shard_seq(_proj(x, p["wq"], H, Dh))
    k = shard_seq(_proj(x, p["wk"], KV, Dh))
    v = shard_seq(_proj(x, p["wv"], KV, Dh))
    q = shard_seq(apply_rope(q, positions, cfg.rope_theta))
    k = shard_seq(apply_rope(k, positions, cfg.rope_theta))
    scale = Dh ** -0.5

    if cfg.attn_impl == "pallas" and causal:
        from ..kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True, window=window,
                                   softcap=cfg.attn_softcap, scale=scale)
    elif cfg.attn_impl in ("blocked", "blocked_unroll"):
        if window is None:
            # unbounded attention: context-parallel shard_map path
            out = _attend_cp(q, k, v, causal=causal,
                             softcap=cfg.attn_softcap, scale=scale,
                             unroll=cfg.attn_impl == "blocked_unroll",
                             scores_f32=cfg.attn_scores_f32)
        else:
            # bounded window: static k slices keep chunks small everywhere
            out = _attend_blocked(q, k, v, causal=causal, window=window,
                                  softcap=cfg.attn_softcap, scale=scale,
                                  scores_f32=cfg.attn_scores_f32)
    else:
        mask = make_causal_mask(S, S, 0, window) if causal else None
        out = _attend_einsum(q, k, v, mask, cfg.attn_softcap, scale)
    out = shard_seq(out)
    y = _unproj(out, p["wo"], x.dtype)
    if return_kv:
        return y, (k, v)
    return y


def cross_attention(p, cfg: ModelConfig, x: jax.Array,
                    enc_kv: Tuple[jax.Array, jax.Array]) -> jax.Array:
    """Decoder->encoder attention; enc_kv are precomputed (B,Se,KV,Dh)."""
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = _proj(x, p["wq"], H, Dh)
    k, v = enc_kv
    out = _attend_einsum(q, k.astype(x.dtype), v.astype(x.dtype), None, None,
                         Dh ** -0.5)
    return _unproj(out, p["wo"], x.dtype)


def encode_cross_kv(p, cfg: ModelConfig, enc_out: jax.Array):
    KV, Dh = cfg.num_kv_heads, cfg.head_dim
    k = _proj(enc_out, p["wk"], KV, Dh)
    v = _proj(enc_out, p["wv"], KV, Dh)
    return k, v


# ---------------------------------------------------------------- KV cache

def _kv_int8(cfg: ModelConfig) -> bool:
    return cfg.kv_cache_dtype == "int8"


def quantize_kv(x: jax.Array):
    """Per-(token, head) symmetric int8.  x: (..., Dh) -> (q, scale(...,1))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.bfloat16)


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    return (q.astype(dtype) * scale.astype(dtype))


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               window: Optional[int], abstract: bool = False) -> Dict:
    """One layer's KV cache.  Local layers get a ring buffer of window size.
    ``kv_cache_dtype='int8'`` stores quantised KV + per-(token,head) scales
    (halves the dominant decode HBM term)."""
    L = min(max_len, window) if window is not None else max_len
    shape = (batch, L, cfg.num_kv_heads, cfg.head_dim)
    sshape = shape[:-1] + (1,)
    if _kv_int8(cfg):
        spec = {"k": (shape, jnp.int8), "v": (shape, jnp.int8),
                "k_scale": (sshape, jnp.bfloat16),
                "v_scale": (sshape, jnp.bfloat16)}
    else:
        dt = jnp.dtype(cfg.dtype)
        spec = {"k": (shape, dt), "v": (shape, dt)}
    if abstract:
        return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in spec.items()}
    return {k: jnp.zeros(s, d) for k, (s, d) in spec.items()}


def cache_logical_axes():
    return ("batch", None, None, None)


def build_cache_from_prefill(cfg: ModelConfig, k: jax.Array, v: jax.Array,
                             max_len: int, window: Optional[int]) -> Dict:
    """Arrange prefill K/V into the decode cache layout.

    Full cache: positions [0, S) land at slots [0, S).  Ring buffer: the last
    ``min(S, W)`` positions land at slot = position % W (so decode writes
    continue seamlessly).
    """
    B, S = k.shape[0], k.shape[1]
    if window is None:
        L = max_len
        if L == S:
            ck, cv = k, v                      # prefill to the brim: no pad
        else:
            ck = jnp.zeros((B, L) + k.shape[2:], k.dtype)
            ck = jax.lax.dynamic_update_slice(ck, k, (0, 0, 0, 0))
            cv = jnp.zeros((B, L) + v.shape[2:], v.dtype)
            cv = jax.lax.dynamic_update_slice(cv, v, (0, 0, 0, 0))
    else:
        L = min(max_len, window)
        n = min(S, L)
        pos = jnp.arange(S - n, S)
        slots = jnp.mod(pos, L)
        ck = jnp.zeros((B, L) + k.shape[2:], k.dtype).at[:, slots].set(k[:, S - n:])
        cv = jnp.zeros((B, L) + v.shape[2:], v.dtype).at[:, slots].set(v[:, S - n:])
    out = {"k": ck, "v": cv}
    if _kv_int8(cfg):
        kq, ks = quantize_kv(ck)
        vq, vs = quantize_kv(cv)
        out = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    # keep the cache sequence-sharded through the layer scan (matches the
    # decode cache layout; otherwise the scan ys buffer replicates over model)
    return {kk: shard(vv, "kv_batch", "kv_seq", None, None)
            for kk, vv in out.items()}


def decode_self_attention(p, cfg: ModelConfig, x: jax.Array, cache: Dict,
                          pos: jax.Array, window: Optional[int]):
    """One-token decode: update cache at ``pos``, attend over it.

    x: (B, 1, D); pos: scalar int32 OR per-slot (B,) vector (continuous
    batching serves requests at different positions in one tick).
    Ring-buffer writes for local layers keep the cache O(window).
    """
    B, _, D = x.shape
    H, KV, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = x.dtype
    q = _proj(x, p["wq"], H, Dh)
    k = _proj(x, p["wk"], KV, Dh)
    v = _proj(x, p["wv"], KV, Dh)
    posb = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1)[:, None],
                            (B, 1))                    # (B,1)
    q = apply_rope(q, posb, cfg.rope_theta)
    k = apply_rope(k, posb, cfg.rope_theta)

    L = cache["k"].shape[1]
    slot = jnp.mod(posb, L) if window is not None else posb      # (B,1)
    # elementwise where-update instead of dynamic_update_slice: DUS on the
    # sequence-sharded cache dim makes the SPMD partitioner all-gather the
    # whole cache per layer (measured +16 GB temp on decode_32k); a select
    # partitions cleanly and fuses into the attention read.
    lidx = jax.lax.broadcasted_iota(jnp.int32, (1, L, 1, 1), 1)
    sel = lidx == slot[:, 0][:, None, None, None]                # (B,L,1,1)
    new_cache = {}
    if _kv_int8(cfg):
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        new_cache["k"] = jnp.where(sel, kq, cache["k"])
        new_cache["v"] = jnp.where(sel, vq, cache["v"])
        new_cache["k_scale"] = jnp.where(sel, ks, cache["k_scale"])
        new_cache["v_scale"] = jnp.where(sel, vs, cache["v_scale"])
        ck = dequantize_kv(new_cache["k"], new_cache["k_scale"], dt)
        cv = dequantize_kv(new_cache["v"], new_cache["v_scale"], dt)
    else:
        new_cache["k"] = jnp.where(sel, k.astype(cache["k"].dtype), cache["k"])
        new_cache["v"] = jnp.where(sel, v.astype(cache["v"].dtype), cache["v"])
        ck, cv = new_cache["k"], new_cache["v"]
    new_cache = {kk: shard(vv, "kv_batch", "kv_seq", None, None)
                 for kk, vv in new_cache.items()}
    ck = shard(ck, "kv_batch", "kv_seq", None, None)
    cv = shard(cv, "kv_batch", "kv_seq", None, None)

    # valid slots: ring buffer holds positions (pos-L, pos]; full cache <= pos
    idx = jnp.arange(L)[None, :]                                 # (1,L)
    if window is not None:
        slot_pos = posb - jnp.mod(slot - idx, L)     # stored position per slot
        valid = (slot_pos >= 0) & (slot_pos > posb - window)     # (B,L)
    else:
        valid = idx <= posb                                      # (B,L)

    if cfg.attn_impl == "pallas":
        from ..kernels import ops as kops
        out = kops.decode_attention(q, ck.astype(dt), cv.astype(dt),
                                    valid, softcap=cfg.attn_softcap,
                                    scale=Dh ** -0.5)
    else:
        mask = valid[:, None, None, :]                           # (B,1,1,L)
        out = _attend_einsum(q, ck.astype(dt), cv.astype(dt), mask,
                             cfg.attn_softcap, Dh ** -0.5)
    y = _unproj(out, p["wo"], dt)
    return y, new_cache
