"""The index maker: corpus, queries and graph, made on the device from a seed.

BANG searches a prebuilt graph; for this benchmark the graph is what weights
are to a model, so every run makes it again from `--seed`:

* Corpus. A Gaussian mixture in a low-dimensional latent space (overlapping
  components), mapped to `d` dimensions by a random linear map, plus small
  isotropic noise: descriptors like SIFT's have an intrinsic dimension far
  below their coordinate count, and so does this corpus.
* Queries. Held-out draws from the same generator.
* Graph. Candidate neighbours come from overlapping k-means partitions (each
  point joins the partitions of its `per_point` nearest centroids; inside a
  partition, exact kNN), as DiskANN builds billion-point indexes. Each point's
  candidates, plus the reverse edges of its partitions' nearest-neighbour
  lists, are cut to `R` by DiskANN's alpha rule (RobustPrune). The entry
  point is the medoid, the point nearest the corpus mean, relabelled as
  point 0.

Everything runs in jitted programs with fixed shapes; nothing is cached on
disk. All distances here are squared L2.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time

import jax
import jax.numpy as jnp
HIGH = jax.lax.Precision.HIGH


@dataclasses.dataclass(frozen=True)
class CorpusSpec:
    n: int
    d: int
    queries: int
    latent_dim: int
    components: int
    center_scale: float
    spread_min: float
    spread_max: float
    noise: float

    @classmethod
    def from_config(cls, cfg: dict) -> "CorpusSpec":
        g = cfg["generator"]
        return cls(n=cfg["n"], d=cfg["d"], queries=cfg["queries"],
                   latent_dim=g["latent_dim"], components=g["components"],
                   center_scale=g["center_scale"], spread_min=g["spread_min"],
                   spread_max=g["spread_max"], noise=g["noise"])


@dataclasses.dataclass(frozen=True)
class GraphSpec:
    R: int
    alpha: float
    block: int          # points per partition block
    per_point: int      # partitions each point joins (overlap)
    knn: int            # nearest neighbours kept per point per block
    reverse_from: int   # of those, how many become reverse edges
    reverse_cap: int    # reverse candidates kept per point
    kmeans_sample: int
    kmeans_iters: int
    chunk: int          # points per step in the assignment pass
    prune_chunk: int    # points per step in the prune pass

    @classmethod
    def from_config(cls, cfg: dict) -> "GraphSpec":
        g = cfg["graph"]
        return cls(R=cfg["R"], alpha=cfg["alpha"], block=g["block"],
                   per_point=g["per_point"], knn=g["knn"],
                   reverse_from=g["reverse_from"],
                   reverse_cap=g["reverse_cap"],
                   kmeans_sample=g["kmeans_sample"],
                   kmeans_iters=g["kmeans_iters"], chunk=g["chunk"],
                   prune_chunk=g["prune_chunk"])


def seed_key(seed: int, salt: int) -> jax.Array:
    """A PRNG key from a seed of any size (more than 32 bits) and a salt."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, salt)


def _min_index(x: jax.Array) -> jax.Array:
    """Index of the first minimum along the last axis (no argmin)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    best = jnp.min(x, axis=-1, keepdims=True)
    return jnp.min(jnp.where(x == best, iota, x.shape[-1]), axis=-1)


# ------------------------------------------------------------------ corpus
def _draw(key, rows: int, spec: CorpusSpec, model) -> jax.Array:
    centers, spreads, lift, offset = model
    ka, kz, ke = jax.random.split(key, 3)
    comp = jax.random.randint(ka, (rows,), 0, spec.components)
    z = centers[comp] + spreads[comp][:, None] * jax.random.normal(
        kz, (rows, spec.latent_dim))
    x = jnp.dot(z, lift, precision=jax.lax.Precision.HIGHEST) + offset
    return x + spec.noise * jax.random.normal(ke, (rows, spec.d))


@functools.partial(jax.jit, static_argnames=("spec",))
def make_corpus(key, spec: CorpusSpec) -> tuple[jax.Array, jax.Array]:
    """(n, d) corpus and (queries, d) held-out queries, float32."""
    km, kd, kq = jax.random.split(key, 3)
    k1, k2, k3, k4 = jax.random.split(km, 4)
    model = (
        spec.center_scale * jax.random.normal(
            k1, (spec.components, spec.latent_dim)),
        jax.random.uniform(k2, (spec.components,), minval=spec.spread_min,
                           maxval=spec.spread_max),
        jax.random.normal(k3, (spec.latent_dim, spec.d)),
        4.0 * jax.random.normal(k4, (spec.d,)),
    )
    steps = -(-spec.n // (1 << 20))
    while spec.n % steps:
        steps += 1
    chunk = spec.n // steps
    parts = jax.lax.map(
        lambda s: _draw(jax.random.fold_in(kd, s), chunk, spec, model),
        jnp.arange(steps))
    data = parts.reshape(spec.n, spec.d)
    return data, _draw(kq, spec.queries, spec, model)


# ------------------------------------------------------------------- graph
def _sq_dists(x: jax.Array, c: jax.Array) -> jax.Array:
    return (jnp.sum(x * x, -1)[:, None] + jnp.sum(c * c, -1)[None, :]
            - 2.0 * jnp.dot(x, c.T, precision=HIGH))


@functools.partial(jax.jit, static_argnames=("k", "sample", "iters"))
def _centroids(data, key, k: int, sample: int, iters: int) -> jax.Array:
    n = data.shape[0]
    idx = jax.random.randint(key, (sample,), 0, n)
    x = data[idx]
    mu = jnp.mean(x, 0)
    x = x - mu

    def lloyd(_, c):
        assign = _min_index(_sq_dists(x, c))
        onehot = jax.nn.one_hot(assign, k, dtype=jnp.float32)
        counts = jnp.sum(onehot, 0)
        sums = jnp.dot(onehot.T, x, precision=jax.lax.Precision.HIGHEST)
        return jnp.where((counts > 0)[:, None],
                         sums / jnp.maximum(counts, 1.0)[:, None], c)

    return jax.lax.fori_loop(0, iters, lloyd, x[:k]) + mu


# Per-point lists cross jit boundaries as flat 1-D arrays: a 2-D array whose
# minor dimension is under 128 is padded to 128 lanes in a TPU's HBM.
def fit_chunk(n: int, want: int) -> int:
    """The largest multiple of 8 up to `want` that divides n, else `want`.

    A chunk that divides n lets a chunked pass stack its outputs without a
    tail copy.
    """
    top = min(want, n)
    for c in range(top - top % 8, 0, -8):
        if n % c == 0:
            return c
    return top


def _chunked(fn, n: int, chunk: int, width: int) -> jax.Array:
    """Flat (n * width,) output of `fn(start)` -> (chunk * width,) over n.

    The last chunk starts early enough to end at row n; its overlap with the
    previous chunk is dropped.
    """
    steps = -(-n // chunk)
    parts = jax.lax.map(lambda s: fn(jnp.minimum(s * chunk, n - chunk)),
                        jnp.arange(steps))
    if steps * chunk == n:
        return parts.reshape(-1)
    tail = parts[-1][(steps * chunk - n) * width:]
    return jnp.concatenate([parts[:-1].reshape(-1), tail])


@functools.partial(jax.jit, static_argnames=("per_point", "chunk"))
def _assign(data, cents, direction, per_point: int, chunk: int):
    """Each point's `per_point` nearest centroids (flat) and its projection."""
    n = data.shape[0]

    def near(start):
        x = jax.lax.dynamic_slice_in_dim(data, start, chunk)
        _, idx = jax.lax.top_k(-_sq_dists(x, cents), per_point)
        return idx.astype(jnp.int32).reshape(-1)

    def proj(start):
        x = jax.lax.dynamic_slice_in_dim(data, start, chunk)
        return jnp.dot(x, direction, precision=HIGH)

    return (_chunked(near, n, chunk, per_point), _chunked(proj, n, chunk, 1))


@functools.partial(jax.jit, static_argnames=("block", "knn", "rows"))
def _block_knn(data, members, valid, block: int, knn: int, rows: int):
    """Exact kNN inside each partition block, flat: entry e's list at
    [e * knn, (e + 1) * knn), nearest first, -1 where the block is short."""

    def one_block(args):
        ids, ok = args
        x = data[ids]
        mu = jnp.sum(jnp.where(ok[:, None], x, 0.0), 0) / jnp.maximum(
            jnp.sum(ok), 1)
        x = x - mu
        sq = jnp.sum(x * x, -1)
        col = jnp.arange(block, dtype=jnp.int32)

        def rows_step(r):
            xr = jax.lax.dynamic_slice_in_dim(x, r * rows, rows)
            d2 = (jax.lax.dynamic_slice_in_dim(sq, r * rows, rows)[:, None]
                  + sq[None, :] - 2.0 * jnp.dot(xr, x.T, precision=HIGH))
            me = r * rows + jnp.arange(rows, dtype=jnp.int32)
            d2 = jnp.where(ok[None, :] & (col[None, :] != me[:, None]), d2,
                           jnp.inf)
            _, local = jax.lax.approx_min_k(d2, knn, recall_target=0.95)
            return jnp.where(ok[local], ids[local], -1).reshape(-1)

        return jax.lax.map(rows_step, jnp.arange(block // rows)).reshape(-1)

    return jax.lax.map(one_block, (members, valid)).reshape(-1)


@functools.partial(jax.jit, static_argnames=("per", "knn", "rf"))
def _reverse_edges(knn_flat, at, per: int, knn: int, rf: int):
    """Edges q -> p for q among the first `rf` of each of p's `per` lists,
    sorted by (target, rank). Returns each target's first slot (n + 1,)
    and the sorted edge ids; edge e comes from point e // (per * rf)."""
    n = at.shape[0] // per
    e = jnp.arange(n * per * rf, dtype=jnp.int32)
    dst = knn_flat[at[e // rf] * knn + e % rf]
    key = jnp.where(dst >= 0, dst * rf + e % rf, jnp.iinfo(jnp.int32).max)
    key_s, edge_s = jax.lax.sort_key_val(key, e)
    starts = jnp.searchsorted(key_s, jnp.arange(n + 1, dtype=jnp.int32) * rf)
    return starts.astype(jnp.int32), edge_s


def _prune_rows(vecs, p, cand, R: int, alpha: float):
    """RobustPrune for a chunk of points: (rows,) points, (rows, L) ids.

    `vecs` is the corpus in bfloat16: one gather of each point's candidate
    rows, whose rounding (about 1% of a neighbour distance) is well inside
    the alpha rule's 20%.
    """
    L = cand.shape[1]
    # Duplicates and self-edges out: sort by id, drop repeats.
    big = jnp.iinfo(jnp.int32).max
    cand = jnp.where((cand < 0) | (cand == p[:, None]), big, cand)
    cand = jnp.sort(cand, -1)
    dup = jnp.concatenate(
        [jnp.zeros_like(cand[:, :1], bool), cand[:, 1:] == cand[:, :-1]], 1)
    valid = (cand != big) & ~dup
    ids = jnp.where(valid, cand, 0)
    x = vecs[p].astype(jnp.float32)
    v = vecs[ids].astype(jnp.float32) - x[:, None, :]        # centred on p
    sq = jnp.sum(v * v, -1)
    pair = (sq[:, :, None] + sq[:, None, :]
            - 2.0 * jnp.einsum("rid,rjd->rij", v, v, precision=HIGH))
    dp = jnp.where(valid, sq, jnp.inf)                        # (rows, L)
    # Nearest first: sort the distances, and permute the pair matrix by a
    # one-hot product (exact at HIGHEST), not by an element gather.
    dp, order = jax.lax.sort_key_val(dp, jnp.broadcast_to(
        jnp.arange(L, dtype=jnp.int32), dp.shape))
    ids = jnp.take_along_axis(ids, order, 1)
    perm = jax.nn.one_hot(order, L, dtype=jnp.float32)      # (rows, r, j)
    hi = jax.lax.Precision.HIGHEST
    pair = jnp.einsum("rai,rij,rbj->rab", perm, pair, perm, precision=hi)
    # DiskANN keeps candidates nearest first, each unless a kept, nearer one
    # occludes it, until R are kept. That sequence is the unique fixed point
    # of `keep[j] = valid[j] and no kept i < j occludes j`; iterating from
    # "keep all" reaches it (in at most L steps, in practice a few), with
    # one batched 0/1 product per step instead of L dependent steps.
    a2 = alpha * alpha
    ok = jnp.isfinite(dp)
    before = jnp.arange(L)[:, None] < jnp.arange(L)[None, :]
    occ = ((a2 * pair <= dp[:, None, :]) & before).astype(jnp.bfloat16)

    def step(state):
        keep, _, it = state
        hits = jnp.einsum("ri,rij->rj", keep.astype(jnp.bfloat16), occ,
                          preferred_element_type=jnp.float32)
        new = ok & (hits == 0)
        return new, jnp.any(new != keep), it + 1

    keep, _, _ = jax.lax.while_loop(
        lambda s: s[1] & (s[2] < L), step, (ok, jnp.bool_(True), 0))
    kept = keep & (jnp.cumsum(keep, 1) <= R)
    # Kept ids first, in distance order; -1 pads the row to R.
    slot = jnp.where(kept, jnp.arange(L, dtype=jnp.int32)[None, :], L)
    slot = jnp.sort(slot, -1)[:, :R]
    out = jnp.take_along_axis(ids, jnp.minimum(slot, L - 1), 1)
    return jnp.where(slot < L, out, -1)


@functools.partial(jax.jit, static_argnames=(
    "per", "knn", "rf", "cap", "R", "alpha", "chunk"))
def _prune_all(vecs, knn_flat, at, starts, edge_s, per: int, knn: int,
               rf: int, cap: int, R: int, alpha: float, chunk: int):
    """Flat (n * R,) adjacency: each point's lists and reverse edges, cut."""
    n = vecs.shape[0]
    last = edge_s.shape[0] - 1

    def rows(start):
        p = start + jnp.arange(chunk, dtype=jnp.int32)
        j = jnp.arange(per * knn, dtype=jnp.int32)
        fwd = knn_flat[at[p[:, None] * per + j // knn] * knn + j % knn]
        pos = starts[p][:, None] + jnp.arange(cap, dtype=jnp.int32)[None, :]
        rev = jnp.where(pos < starts[p + 1][:, None],
                        edge_s[jnp.minimum(pos, last)] // (per * rf), -1)
        cand = jnp.concatenate([fwd, rev], 1)
        return _prune_rows(vecs, p, cand, R, alpha).reshape(-1)

    return _chunked(rows, n, chunk, R)


@jax.jit
def _medoid(data) -> jax.Array:
    mu = jnp.mean(data, 0)
    return _min_index(jnp.sum((data - mu) ** 2, -1)[None, :])[0]


def build_graph(data: jax.Array, key, spec: GraphSpec, release,
                log=None) -> jax.Array:
    """The flat (n * R,) int32 adjacency (-1 padded), entry point 0.

    The corpus passed in is donated: relabelled so that the medoid is point
    0, it is handed to `release(data)` before the prune, which reads a
    bfloat16 copy; the caller keeps what it needs of it there.
    """
    n = data.shape[0]
    P, per, K = spec.block, spec.per_point, spec.knn
    if n < 2 * P:
        raise ValueError(f"n = {n} is too small for blocks of {P}")
    stamp = _Stamp(log)
    k_c, k_dir = jax.random.split(key)
    n_cent = max(2, round(per * n / P))
    cents = _centroids(data, k_c, n_cent, min(spec.kmeans_sample, n),
                       spec.kmeans_iters)
    direction = jax.random.normal(k_dir, (data.shape[1],))
    near, proj = _assign(data, cents, direction, per,
                         fit_chunk(n, spec.chunk))
    stamp("graph_partition_s", near)

    # Sort the n * per (centroid, point) memberships by centroid, then by
    # projection, and cut them into blocks of P.
    m = n * per
    blocks = -(-m // P)
    pad = blocks * P - m
    cent = jnp.concatenate([near, jnp.full((pad,), n_cent, jnp.int32)])
    pr = jnp.concatenate([jnp.repeat(proj, per), jnp.zeros((pad,))])
    pid = jnp.concatenate([jnp.repeat(jnp.arange(n, dtype=jnp.int32), per),
                           jnp.zeros((pad,), jnp.int32)])
    cent_s, _, pid_s = jax.lax.sort((cent, pr, pid), num_keys=2)
    del cent, pr, pid, near, proj
    ok = cent_s < n_cent
    knn_flat = _block_knn(data, pid_s.reshape(blocks, P),
                          ok.reshape(blocks, P), P, K, math.gcd(P, 2048))
    stamp("graph_knn_s", knn_flat)

    # Where each point's `per` lists sit: sort entries by point id (every
    # point has exactly `per` entries; padding sorts last).
    _, at = jax.lax.sort_key_val(jnp.where(ok, pid_s, n),
                                 jnp.arange(blocks * P, dtype=jnp.int32))
    at = at[:m]
    del cent_s, pid_s, ok
    rf = spec.reverse_from
    starts, edge_s = _reverse_edges(knn_flat, at, per, K, rf)
    stamp("graph_reverse_s", edge_s)

    # The prune reads a bfloat16 copy; the float32 corpus, relabelled so the
    # medoid is point 0, goes to `release` and leaves the device first.
    medoid = _medoid(data)
    vecs = data.astype(jnp.bfloat16)
    release(_swap_rows(data, medoid))
    del data
    stamp.restart()
    adj = _prune_all(vecs, knn_flat, at, starts, edge_s, per, K, rf,
                     spec.reverse_cap, spec.R, spec.alpha,
                     fit_chunk(n, spec.prune_chunk))
    del vecs, knn_flat, at, starts, edge_s
    adj = _relabel(adj, medoid, spec.R)
    stamp("graph_prune_s", adj)
    return adj


# The entry point is a constant of the compiled search: with the medoid
# relabelled as point 0, every seed's executables are one program, which
# the persistent compile cache then holds.
@functools.partial(jax.jit, donate_argnums=0)
def _swap_rows(data, medoid):
    rows = jnp.stack([jnp.int32(0), medoid])
    return data.at[rows].set(data[rows[::-1]])


@functools.partial(jax.jit, static_argnames=("R",), donate_argnums=0)
def _relabel(adj, medoid, R: int):
    """The flat adjacency with rows 0 and `medoid`, and those ids, swapped."""
    row0 = jax.lax.dynamic_slice(adj, (0,), (R,))
    rowm = jax.lax.dynamic_slice(adj, (medoid * R,), (R,))
    adj = jax.lax.dynamic_update_slice(adj, rowm, (0,))
    adj = jax.lax.dynamic_update_slice(adj, row0, (medoid * R,))
    return jnp.where(adj == 0, medoid, jnp.where(adj == medoid, 0, adj))


class _Stamp:
    """Seconds per stage, each ending when its result is ready."""

    def __init__(self, log) -> None:
        self.log = log
        self.t = time.perf_counter()

    def restart(self) -> None:
        self.t = time.perf_counter()

    def __call__(self, name: str, value) -> None:
        jax.block_until_ready(value)
        now = time.perf_counter()
        if self.log is not None:
            self.log(name, now - self.t)
        self.t = now
