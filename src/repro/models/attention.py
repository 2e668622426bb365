"""GQA attention with local windows, RoPE/M-RoPE, KV caches, chunked scores.

Memory discipline:
  * train/prefill: scores computed in query chunks (``Q_CHUNK``) so the
    (S x S) matrix never materializes (bounded 32k-prefill activations);
  * decode, global layers: full-length cache, masked by key position;
  * decode, local layers: **ring-buffer cache of `window` entries** — the
    gemma3/recurrentgemma long-context play; a 500k-token stream costs
    O(window) memory on local layers. Keys carry absolute positions, so
    masking is uniform: valid = (kpos <= q) & (kpos > q - window).

All projections route through ft_einsum (paper ABFT, config-switched).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.dist import sharding as shd
from repro.models import layers as L
from repro.ft.abft_dense import ft_einsum

Q_CHUNK = 1024
NEG_POS = -(1 << 30)


def _tp_size() -> int:
    mesh = shd.active_mesh()
    return mesh.shape.get("model", 1) if mesh is not None else 1


class KVCache(NamedTuple):
    k: jax.Array          # (B, Len, KV, hd)
    v: jax.Array          # (B, Len, KV, hd)
    positions: jax.Array  # (Len,) int32 absolute positions (NEG_POS = empty)


def init_cache(cfg, batch: int, max_len: int, *, window: int = 0,
               dtype=jnp.bfloat16, abstract: bool = False):
    """window > 0 -> ring buffer of `window` entries."""
    length = min(max_len, window) if window else max_len
    kv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract else \
         (lambda s, d: jnp.zeros(s, d))
    pos = mk((length,), jnp.int32) if abstract else \
        jnp.full((length,), NEG_POS, jnp.int32)
    return KVCache(mk((batch, length, kv, hd), dtype),
                   mk((batch, length, kv, hd), dtype), pos)


def init_attention(key, cfg, dtype):
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    specs = {
        "wq": ((d, cfg.num_heads, hd), ("embed", "heads", "head_dim")),
        "wk": ((d, cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ((d, cfg.num_kv_heads, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ((cfg.num_heads, hd, d), ("heads", "head_dim", "embed")),
    }
    return L.build(key, specs, dtype)


def _block_attend(q, k, v, mask):
    """q (B,Sq,KV,G,hd), k/v (B,Skv,KV,hd), mask (Sq, Skv) or (B,Sq,Skv)."""
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                   preferred_element_type=jnp.float32)
    while mask.ndim < s.ndim:
        mask = mask[None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # rows with no valid key (e.g. cold ring slots) -> zero output
    any_valid = jnp.any(mask, axis=-1, keepdims=True)
    p = jnp.where(any_valid, p, 0.0)
    return jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)


def _attend_local(q, k, v, *, q_positions, kv_positions, causal, window,
                  chunk):
    """Chunked attention on local (per-device) arrays."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    qg = (q * hd ** -0.5).reshape(b, sq, kvh, h // kvh, hd)

    def mask_for(qpos):
        m = (kv_positions >= 0)[None, :]
        if causal:
            m = m & (kv_positions[None, :] <= qpos[:, None])
        if window:
            m = m & (kv_positions[None, :] > qpos[:, None] - window)
        return m

    if sq <= chunk:
        return _block_attend(qg, k, v, mask_for(q_positions)).reshape(
            b, sq, h, hd)
    n, rem = divmod(sq, chunk)
    main = n * chunk
    qs = qg[:, :main].reshape(b, n, chunk, kvh, h // kvh, hd).transpose(
        1, 0, 2, 3, 4, 5)
    qp = q_positions[:main].reshape(n, chunk)
    out = jax.lax.map(
        lambda args: _block_attend(args[0], k, v, mask_for(args[1])),
        (qs, qp))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, main, h, hd)
    if rem:   # tail chunk (e.g. whisper's 1500-frame encoder)
        tail = _block_attend(qg[:, main:], k, v,
                             mask_for(q_positions[main:])).reshape(
            b, rem, h, hd)
        out = jnp.concatenate([out, tail], axis=1)
    return out


def attend(q, k, v, *, q_positions, kv_positions, causal: bool = True,
           window: int = 0, chunk: int = Q_CHUNK):
    """Position-masked attention. q (B,Sq,H,hd); k/v (B,Skv,KV,hd).

    q_positions (Sq,), kv_positions (Skv,) are absolute. Mask:
      valid = kpos >= 0 & (causal -> kpos <= qpos)
                        & (window -> kpos > qpos - window)

    Context parallelism (train/prefill): the query sequence is sharded
    over the 'model' axis with an EXPLICIT shard_map — q/scores/output
    per-device, k/v replicated across TP. Head counts (40, 36, 28, 8, ...)
    don't divide TP=16 across the assigned archs; sharding the contracted
    head_dim all-reduces full f32 scores, and constraint-based seq
    sharding left GSPMD free to re-gather 3 GiB score chunks in the
    backward (§Perf nemotron iterations 0-2) — shard_map makes the
    collective schedule deterministic: none in attention itself, small
    psums for the k/v gradients only.
    """
    from jax.sharding import PartitionSpec as P

    mesh = shd.active_mesh()
    b, sq = q.shape[0], q.shape[1]
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    if mesh is None or tp <= 1 or sq <= 1 or sq % tp != 0:
        return _attend_local(q, k, v, q_positions=q_positions,
                             kv_positions=kv_positions, causal=causal,
                             window=window, chunk=chunk)

    daxes = shd.data_axes(mesh)
    dp = 1
    for a in daxes:
        dp *= mesh.shape[a]
    brow = (daxes if len(daxes) > 1 else daxes[0]) \
        if (b % dp == 0 and b >= dp) else None
    local_chunk = max(min(chunk, sq // tp), 128)

    def body(q, k, v, qpos, kvpos):
        return _attend_local(q, k, v, q_positions=qpos, kv_positions=kvpos,
                             causal=causal, window=window, chunk=local_chunk)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(brow, "model", None, None),
                  P(brow, None, None, None),
                  P(brow, None, None, None),
                  P("model"), P(None)),
        out_specs=P(brow, "model", None, None),
        check_vma=False,
    )(q, k, v, q_positions, kv_positions)


def apply_attention(cfg, params, x, *, positions, causal=True, window=0,
                    cache: Optional[KVCache] = None, pos=None,
                    kv_input=None, make_cache=False, max_len=0):
    """Attention block: projections + rope + (cache r/w) + attend + out proj.

    Modes:
      * train:            cache=None, make_cache=False
      * prefill:          cache=None, make_cache=True (returns fresh cache
                          of length max_len holding this call's k/v)
      * decode:           cache + scalar pos (one new token)
      * cross-attention:  kv_input = encoder states (no rope, no cache)
    """
    kv_src = kv_input if kv_input is not None else x
    # Sequence parallelism (Megatron-SP flavoured): head counts (40, 36,
    # 28, 8, ...) don't divide TP=16 across the assigned archs, so the
    # QKV/out projections and score/value products shard over the *query
    # sequence* instead. k/v are re-gathered across TP for the attend
    # (bf16, ~D bytes/token — cheap next to f32 score all-reduces).
    if x.shape[1] > 1 and x.shape[1] % _tp_size() == 0:
        x = shd.constrain(x, ("batch", "seq_tp", None))
    q = ft_einsum("bsd,dhk->bshk", x, params["wq"])
    k = ft_einsum("bsd,dhk->bshk", kv_src, params["wk"])
    v = ft_einsum("bsd,dhk->bshk", kv_src, params["wv"])

    if kv_input is not None:
        # cross-attention: every encoder frame visible, no rope.
        skv = k.shape[1]
        out = attend(q, k, v, q_positions=jnp.zeros((x.shape[1],), jnp.int32),
                     kv_positions=jnp.zeros((skv,), jnp.int32), causal=False)
        return ft_einsum("bshk,hkd->bsd", out, params["wo"]), cache

    q = L.apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
    k = L.apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    pos1d = positions if positions.ndim == 2 else positions[..., 0]

    if cache is not None:
        # decode: write (k, v, pos) into the (ring) buffer, attend over it.
        length = cache.k.shape[1]
        slot = pos % length
        ck = jax.lax.dynamic_update_slice(
            cache.k, k.astype(cache.k.dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            cache.v, v.astype(cache.v.dtype), (0, slot, 0, 0))
        cpos = jax.lax.dynamic_update_slice(
            cache.positions, pos[None].astype(jnp.int32)
            if jnp.ndim(pos) == 0 else pos.astype(jnp.int32), (slot,))
        new_cache = KVCache(ck, cv, cpos)
        out = attend(q, ck, cv, q_positions=pos1d[0],
                     kv_positions=cpos, causal=True, window=window)
    elif make_cache:
        sq = x.shape[1]
        length = min(max_len, window) if window else max_len
        pad = length - sq
        if pad >= 0:
            ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            cpos = jnp.pad(pos1d[0], (0, pad), constant_values=NEG_POS)
        else:  # prefill longer than ring: keep the tail, preserving the
            # ring invariant slot(p) = p % length so decode writes land
            # on the oldest entry.
            ck, cv = k[:, -length:], v[:, -length:]
            cpos = pos1d[0][-length:]
            shift = (sq - length) % length
            ck = jnp.roll(ck, shift, axis=1)
            cv = jnp.roll(cv, shift, axis=1)
            cpos = jnp.roll(cpos, shift, axis=0)
        new_cache = KVCache(ck, cv, cpos.astype(jnp.int32))
        out = attend(q, k, v, q_positions=pos1d[0],
                     kv_positions=pos1d[0], causal=causal, window=window)
    else:
        new_cache = None
        out = attend(q, k, v, q_positions=pos1d[0],
                     kv_positions=pos1d[0], causal=causal, window=window)

    return ft_einsum("bshk,hkd->bsd", out, params["wo"]), new_cache
