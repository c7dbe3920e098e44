"""Worklist/merge properties (paper §4.7-4.8)."""
import numpy as np
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # clean environment: seeded-random fallback shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core.worklist import (
    INVALID_ID,
    Worklist,
    first_unvisited,
    mark_visited,
    merge_path_reference,
    merge_worklist,
    sort_candidates,
    worklist_init,
)

# XLA flushes subnormals to zero and numpy does not, so a subnormal key
# would compare differently on the two sides.
finite_f32 = st.floats(-1e6, 1e6, width=32, allow_nan=False,
                       allow_subnormal=False)


@settings(max_examples=40, deadline=None)
@given(st.lists(finite_f32, min_size=1, max_size=40), st.data())
def test_merge_keeps_t_smallest_union(dists, data):
    """Merged worklist == t smallest of (worklist ∪ candidates)."""
    n1 = data.draw(st.integers(1, len(dists)))
    d1, d2 = sorted(dists[:n1]), sorted(dists[n1:])
    t = len(d1)
    wl = Worklist(
        dists=jnp.asarray([d1], jnp.float32),
        ids=jnp.asarray([list(range(t))], jnp.int32),
        visited=jnp.zeros((1, t), bool),
    )
    cd = jnp.asarray([d2], jnp.float32) if d2 else jnp.full((1, 0), np.inf, jnp.float32)
    ci = jnp.asarray([[100 + i for i in range(len(d2))]], jnp.int32)
    out = merge_worklist(wl, cd, ci)
    expect = sorted(d1 + d2)[:t]
    np.testing.assert_allclose(np.asarray(out.dists[0]), expect, rtol=1e-6)


@settings(max_examples=40, deadline=None)
@given(
    st.lists(finite_f32, min_size=1, max_size=32),
    st.lists(finite_f32, min_size=1, max_size=32),
)
def test_merge_path_equals_sorted_concat(a, b):
    a, b = sorted(a), sorted(b)
    d1 = jnp.asarray([a], jnp.float32)
    i1 = jnp.asarray([list(range(len(a)))], jnp.int32)
    d2 = jnp.asarray([b], jnp.float32)
    i2 = jnp.asarray([[1000 + i for i in range(len(b))]], jnp.int32)
    od, oi = merge_path_reference(d1, i1, d2, i2)
    # expectation computed from the jnp-roundtripped values (CPU flushes
    # subnormals to zero; the algorithm must match what the device sees)
    expect = np.sort(np.concatenate([np.asarray(d1[0]), np.asarray(d2[0])]))
    np.testing.assert_allclose(np.asarray(od[0]), expect, rtol=1e-6)
    # the output must be a permutation of the inputs (ids preserved)
    assert set(np.asarray(oi[0]).tolist()) == set(range(len(a))) | {1000 + i for i in range(len(b))}


def test_first_unvisited_and_mark():
    wl = worklist_init(2, 4)
    wl = Worklist(
        dists=jnp.asarray([[0.1, 0.2, 0.3, np.inf], [0.5, 0.6, np.inf, np.inf]], jnp.float32),
        ids=jnp.asarray([[7, 8, 9, INVALID_ID], [3, 4, INVALID_ID, INVALID_ID]], jnp.int32),
        visited=jnp.asarray([[True, False, False, True], [True, True, True, True]]),
    )
    ids, found = first_unvisited(wl)
    assert ids[0] == 8 and bool(found[0])
    assert ids[1] == INVALID_ID and not bool(found[1])
    wl2 = mark_visited(wl, jnp.asarray([8, INVALID_ID], jnp.int32))
    assert bool(wl2.visited[0, 1])


@settings(max_examples=25, deadline=None)
@given(st.lists(finite_f32, min_size=1, max_size=64))
def test_sort_candidates_matches_numpy(vals):
    d = jnp.asarray([vals], jnp.float32)
    i = jnp.asarray([list(range(len(vals)))], jnp.int32)
    sd, si = sort_candidates(d, i)
    np.testing.assert_allclose(np.asarray(sd[0]), np.sort(np.asarray(vals, np.float32)))


# --------------------------------------------------------------- edge cases
@settings(max_examples=25, deadline=None)
@given(st.lists(finite_f32, min_size=4, max_size=24), st.data())
def test_merge_into_saturated_worklist_keeps_t_best(vals, data):
    """A saturated worklist (every slot finite, no padding) must evict
    exactly the worst entries when better candidates arrive, and stay
    sorted with untouched-entry flags preserved."""
    t = data.draw(st.integers(2, max(2, len(vals) // 2)))
    wl_d = sorted(vals[:t])
    cand = sorted(vals[t:]) or [1e9]
    wl = Worklist(
        dists=jnp.asarray([wl_d], jnp.float32),
        ids=jnp.asarray([list(range(t))], jnp.int32),
        visited=jnp.asarray([[i % 2 == 0 for i in range(t)]]),
    )
    out = merge_worklist(
        wl,
        jnp.asarray([cand], jnp.float32),
        jnp.asarray([[1000 + i for i in range(len(cand))]], jnp.int32),
    )
    expect = sorted(wl_d + cand)[:t]
    np.testing.assert_allclose(np.asarray(out.dists[0]), expect, rtol=1e-6)
    got = np.asarray(out.dists[0])
    assert (got[:-1] <= got[1:]).all(), "worklist must stay sorted"
    # Survivor slots that came from the worklist keep their visited flag;
    # freshly merged candidates always enter unvisited.
    for pos, nid in enumerate(np.asarray(out.ids[0]).tolist()):
        if nid >= 1000:
            assert not bool(out.visited[0, pos])


@settings(max_examples=25, deadline=None)
@given(st.lists(finite_f32, min_size=1, max_size=16), st.data())
def test_merge_duplicate_inserts_stay_sorted_and_bounded(vals, data):
    """Duplicate candidate ids (the bloom filter normally guarantees none,
    but the worklist must not corrupt if they appear): the merge keeps the
    t smallest of the multiset union, sorted, length exactly t."""
    t = data.draw(st.integers(1, len(vals)))
    wl_d = sorted(vals)[:t]
    wl = Worklist(
        dists=jnp.asarray([wl_d], jnp.float32),
        ids=jnp.asarray([list(range(t))], jnp.int32),
        visited=jnp.zeros((1, t), bool),
    )
    dup = [vals[0]] * data.draw(st.integers(1, 6))   # same dist, same id
    cd = jnp.asarray([sorted(dup)], jnp.float32)
    ci = jnp.full((1, len(dup)), 777, jnp.int32)
    out = merge_worklist(wl, cd, ci)
    assert out.dists.shape == (1, t)
    expect = sorted(wl_d + dup)[:t]
    np.testing.assert_allclose(np.asarray(out.dists[0]), expect, rtol=1e-6)
    got = np.asarray(out.dists[0])
    assert (got[:-1] <= got[1:]).all()


def test_all_visited_frontier_reports_no_candidate():
    """When every slot is visited (the convergence condition of Algorithm 2)
    first_unvisited must report found=False with the INVALID sentinel for
    every lane -- including a fully padded (fresh) worklist."""
    wl = Worklist(
        dists=jnp.asarray([[0.1, 0.2, 0.3]], jnp.float32),
        ids=jnp.asarray([[4, 5, 6]], jnp.int32),
        visited=jnp.ones((1, 3), bool),
    )
    ids, found = first_unvisited(wl)
    assert not bool(found[0]) and ids[0] == INVALID_ID
    fresh = worklist_init(2, 4)         # padding slots are born visited
    ids, found = first_unvisited(fresh)
    assert not np.asarray(found).any()
    assert (np.asarray(ids) == int(INVALID_ID)).all()


def test_mark_visited_with_sentinel_is_noop_on_real_entries():
    """Converged lanes mark INVALID_ID: only padding slots (which are
    already visited) may match, so real entries never flip."""
    wl = Worklist(
        dists=jnp.asarray([[0.1, 0.2, np.inf]], jnp.float32),
        ids=jnp.asarray([[4, 5, INVALID_ID]], jnp.int32),
        visited=jnp.asarray([[False, False, True]]),
    )
    out = mark_visited(wl, jnp.asarray([INVALID_ID], jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(out.visited), np.asarray(wl.visited)
    )
    # And marking a real id flips exactly that slot.
    out2 = mark_visited(wl, jnp.asarray([5], jnp.int32))
    assert bool(out2.visited[0, 1]) and not bool(out2.visited[0, 0])
