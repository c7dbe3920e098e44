"""BANG-KV: the paper's pipeline as long-context decode attention.

For the assigned `long_500k` cells, exact attention over a 512k-token KV
cache is quadratic-in-context and memory-bound on the full-precision keys.
BANG's three stages map directly (DESIGN.md §4):

  Stage 1 (PQDistTable)  per new query token, a (H, m, 256) table of
                         q-subvector x centroid *dot products* -- PQ adapted
                         from L2 to MIPS, since attention scores are inner
                         products (the identity table[j,c] = q_j . cb[j,c]
                         makes ADC sums exact-in-expectation scores).
  Stage 2 (ADC search)   approximate scores for ALL cached keys from the
                         uint8 codes (m bytes/key vs 2·hd full precision --
                         the same "compressed data near compute" split), then
                         top-L selection. The KV cache is append-only during
                         decode, so the flat ADC scan replaces the Vamana
                         traversal (building a graph per decode step is not
                         in the paper; its offline index assumption breaks --
                         noted in DESIGN.md §Arch-applicability).
  Stage 3 (re-rank)      exact scores on the retrieved L keys' full vectors
                         plus an exact recent window; softmax + weighted sum
                         over the union.

The codes are the near-memory object (replicated or sequence-sharded), the
full K/V are the far-memory object (sequence-sharded over `model`); only
top-L rows are gathered -- the PCIe-frugality insight at ICI scale.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.compat import shard_map

from .layers import apply_rope, truncated_normal_init

Array = jax.Array


class BangKVCache(NamedTuple):
    codes: Array    # (B, S_max, Hkv, m) uint8 -- PQ codes of keys (near memory)
    k: Array        # (B, S_max, Hkv, hd)      -- full keys (far memory)
    v: Array        # (B, S_max, Hkv, hd)      -- full values (far memory)
    index: Array    # () int32


def bangkv_codebook_params(key, n_kv_heads: int, head_dim: int, m: int) -> Array:
    """Per-KV-head PQ codebooks (Hkv, m, 256, hd/m), trained offline or from
    prefill keys (fit_codebooks); random init is shape/flow-correct."""
    dsub = head_dim // m
    return truncated_normal_init(key, (n_kv_heads, m, 256, dsub), scale=1.0, dtype=jnp.float32)


def encode_keys(codebooks: Array, k: Array) -> Array:
    """PQ-encode keys: (B, S, Hkv, hd) -> (B, S, Hkv, m) uint8 (L2 argmin)."""
    B, S, Hkv, hd = k.shape
    m, dsub = codebooks.shape[1], codebooks.shape[3]
    ks = k.astype(jnp.float32).reshape(B, S, Hkv, m, dsub)
    # d2[b,s,h,j,c] = ||ks - cb[h,j,c]||^2 ; argmin over c
    d2 = (
        jnp.sum(ks * ks, -1)[..., None]
        + jnp.sum(codebooks * codebooks, -1)[None, None]
        - 2.0 * jnp.einsum("bshjd,hjcd->bshjc", ks, codebooks)
    )
    return jnp.argmin(d2, axis=-1).astype(jnp.uint8)


def fit_codebooks(k: Array, m: int, iters: int = 8) -> Array:
    """Train per-head codebooks on (B, S, Hkv, hd) prefill keys."""
    from repro.core.kmeans import kmeans_per_subspace

    B, S, Hkv, hd = k.shape
    dsub = hd // m
    flat = k.astype(jnp.float32).transpose(2, 0, 1, 3).reshape(Hkv, B * S, m, dsub)

    def per_head(kh):  # (BS, m, dsub)
        return kmeans_per_subspace(kh.transpose(1, 0, 2), 256, iters)

    return jax.vmap(per_head)(flat)                    # (Hkv, m, 256, dsub)


def bangkv_init(batch: int, s_max: int, n_kv_heads: int, head_dim: int, m: int,
                dtype=jnp.bfloat16) -> BangKVCache:
    return BangKVCache(
        codes=jnp.zeros((batch, s_max, n_kv_heads, m), jnp.uint8),
        k=jnp.zeros((batch, s_max, n_kv_heads, head_dim), dtype),
        v=jnp.zeros((batch, s_max, n_kv_heads, head_dim), dtype),
        index=jnp.zeros((), jnp.int32),
    )


def _retrieve_top_l(approx: Array, top_l: int, hier: bool) -> Array:
    """Stage-2 selection. hier=True: shard-local top-L via shard_map, then a
    global top-L over NC*L survivors.

    XLA's SPMD partitioner replicates sort/top-k operands, so a flat
    lax.top_k over the sequence-sharded (B, H, S) scores all-gathers S f32
    per head per layer. The shard_map pins the first stage to shard-local
    execution; only (B, H, NC, L) values+ids cross the wire -- S/(NC*L)x
    fewer collective bytes.
    """
    B, H, S = approx.shape
    mesh = jax.sharding.get_abstract_mesh()
    names = tuple(mesh.axis_names)
    have_model = "model" in names
    NC = mesh.shape["model"] if have_model else 0
    if not (hier and have_model and NC and S % NC == 0 and S // NC >= top_l):
        return jax.lax.top_k(approx, top_l)[1]

    from jax.sharding import PartitionSpec as P

    # Head parallelism over the DP axes: long-context decode is batch=1, so
    # the data axis is idle -- ride it on H instead of letting GSPMD invent
    # (and then all-gather) that sharding itself.
    dp = tuple(a for a in ("pod", "data") if a in names)
    dp_total = 1
    for a_ in dp:
        dp_total *= mesh.shape[a_]
    h_axis = (dp if len(dp) > 1 else dp[0]) if (dp and H % dp_total == 0) else None

    a = approx.reshape(B, H, NC, S // NC)

    def local_topk(a_loc):
        lv, li = jax.lax.top_k(a_loc, top_l)                     # (B,h,1,L) x2
        return lv, li

    spec = P(None, h_axis, "model", None)
    lv, li = shard_map(
        local_topk, mesh=mesh, in_specs=spec, out_specs=(spec, spec)
    )(a)
    li = li + (jnp.arange(NC, dtype=jnp.int32) * (S // NC))[None, None, :, None]
    _, gpos = jax.lax.top_k(lv.reshape(B, H, NC * top_l), top_l)
    return jnp.take_along_axis(li.reshape(B, H, NC * top_l), gpos, axis=-1)


def bangkv_decode_attention(
    codebooks: Array,        # (Hkv, m, 256, dsub)
    q: Array,                # (B, 1, H, hd), rope applied
    cache: BangKVCache,      # with the NEW key already appended
    *,
    top_l: int,
    window: int,
    hier_topk: bool = False,  # opt_hier_topk: shard-local then global top-k
    adc_lite: bool = False,   # opt_adc_lite: clip-mode + bf16 ADC gather
) -> Array:
    """Stages 1-3 for one decode step. Returns (B, 1, H, hd)."""
    from repro.distributed.partitioning import TP_AXIS, constrain

    B, _, H, hd = q.shape
    _, S, Hkv, m = cache.codes.shape
    G = H // Hkv
    dsub = hd // m
    scale = hd ** -0.5

    # ---- Stage 1: per-(query-head) dot-product PQDistTable.
    qf = q.astype(jnp.float32).reshape(B, H, m, dsub)
    # table[b, h, j, c] = q_sub . cb[kv(h), j, c]
    cb_per_q = jnp.repeat(codebooks, G, axis=0)                  # (H, m, 256, dsub)
    table = jnp.einsum("bhjd,hjcd->bhjc", qf, cb_per_q)          # (B, H, m, 256)

    # ---- Stage 2: ADC scores for every cached key, from codes alone.
    idx = cache.codes.astype(jnp.int32)                          # (B, S, Hkv, m)
    idx_q = jnp.repeat(idx, G, axis=2)                           # (B, S, H, m)
    tbl = table.astype(jnp.bfloat16) if adc_lite else table
    gathered = jnp.take_along_axis(
        tbl[:, None],                                            # (B, 1, H, m, 256)
        idx_q[..., None],                                        # (B, S, H, m, 1)
        axis=4,
        **({"mode": "clip"} if adc_lite else {}),
    )[..., 0]                                                    # (B, S, H, m)
    approx = jnp.sum(gathered.astype(jnp.float32), axis=-1).transpose(0, 2, 1)

    pos = jnp.arange(S, dtype=jnp.int32)
    in_window = (pos[None, :] >= cache.index - window) & (pos[None, :] < cache.index)
    valid_hist = (pos[None, :] < cache.index) & ~in_window       # retrieval region
    approx = jnp.where(valid_hist[:, None], approx, -jnp.inf)    # (B, H, S)

    # top-L retrieval per query head over the compressed scores
    top_idx = _retrieve_top_l(approx, top_l, hier_topk)          # (B, H, L)

    # ---- Stage 3: exact re-rank over retrieved ∪ recent-window keys.
    kv_head = (jnp.arange(H, dtype=jnp.int32) // G)[None, :, None]
    b_idx = jnp.arange(B, dtype=jnp.int32)[:, None, None]
    k_sel = cache.k[b_idx, top_idx, kv_head].astype(jnp.float32)  # (B, H, L, hd)
    v_sel = cache.v[b_idx, top_idx, kv_head].astype(jnp.float32)
    qh = q.astype(jnp.float32).reshape(B, H, hd)
    s_ret = jnp.einsum("bhd,bhld->bhl", qh, k_sel) * scale       # (B, H, L)
    # a retrieved slot may be invalid when history < L: the retrieval region
    # is exactly pos < index - window, so validity is index arithmetic (no
    # gather of a (B, H, S) mask).
    ret_valid = top_idx < (cache.index - window)
    s_ret = jnp.where(ret_valid, s_ret, -jnp.inf)

    # exact recent window (includes the brand-new key). NOTE: a dynamic_slice
    # here all-gathers the entire sharded cache (measured 32 GiB/step);
    # the fancy gather partitions owner-side and moves only the window rows.
    w_idx = cache.index - window + jnp.arange(window, dtype=jnp.int32)  # may underflow; mask
    w_valid = w_idx >= 0
    w_safe = jnp.clip(w_idx, 0, S - 1)
    k_win = cache.k[:, w_safe].astype(jnp.float32)               # (B, W, Hkv, hd)
    v_win = cache.v[:, w_safe].astype(jnp.float32)
    qg = qh.reshape(B, Hkv, G, hd)
    s_win = jnp.einsum("bkgd,bwkd->bkgw", qg, k_win) * scale
    s_win = jnp.where(w_valid[None, None, None], s_win, -jnp.inf)
    s_win = s_win.reshape(B, H, window)

    # joint softmax over [retrieved, window]
    s_all = jnp.concatenate([s_ret, s_win], axis=-1)             # (B, H, L+W)
    p_all = jax.nn.softmax(s_all, axis=-1)
    p_ret, p_win = p_all[..., :top_l], p_all[..., top_l:]
    out = jnp.einsum("bhl,bhld->bhd", p_ret, v_sel)
    out = out + jnp.einsum(
        "bkgw,bwkd->bkgd", p_win.reshape(B, Hkv, G, window), v_win
    ).reshape(B, H, hd)
    return out[:, None].reshape(B, 1, H, hd).astype(q.dtype)


def bangkv_attention_block(
    p: dict,                  # attention params (wq/wk/wv/wo)
    codebooks: Array,
    x: Array,                 # (B, 1, D)
    cache: BangKVCache,
    *,
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    rope_theta: Array | float,
    top_l: int,
    window: int,
    hier_topk: bool = False,
    adc_lite: bool = False,
) -> tuple[Array, BangKVCache]:
    """Decode attention sublayer with the BANG-KV cache."""
    B, S1, _ = x.shape
    q = (x @ p["wq"]).reshape(B, 1, n_heads, head_dim)
    k = (x @ p["wk"]).reshape(B, 1, n_kv_heads, head_dim)
    v = (x @ p["wv"]).reshape(B, 1, n_kv_heads, head_dim)
    pos = cache.index[None, None]
    q = apply_rope(q, jnp.broadcast_to(pos, (B, 1)), rope_theta)
    k = apply_rope(k, jnp.broadcast_to(pos, (B, 1)), rope_theta)

    codes_new = encode_keys(codebooks, k)                        # (B, 1, Hkv, m)
    upd = lambda buf, val: jax.lax.dynamic_update_slice_in_dim(
        buf, val.astype(buf.dtype), cache.index, axis=1
    )
    new_cache = BangKVCache(
        codes=upd(cache.codes, codes_new),
        k=upd(cache.k, k),
        v=upd(cache.v, v),
        index=cache.index + 1,
    )
    out = bangkv_decode_attention(
        codebooks, q, new_cache, top_l=top_l, window=window,
        hier_topk=hier_topk, adc_lite=adc_lite,
    )
    y = out.reshape(B, 1, n_heads * head_dim) @ p["wo"]
    return y, new_cache
