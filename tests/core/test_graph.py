"""Graph construction: ``from_coo``'s CSR/CSC pointers against a
brute-force count, and ``from_dst_sorted`` against ``from_coo`` on the
same padded edge lists."""
import zlib

import numpy as np
import pytest

from repro.core.graph import from_coo, from_dst_sorted


def _coo_case(case):
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    if case == "duplicates":      # 8x8 ids, 200 edges: parallel edges
        return rng.integers(0, 8, 200), rng.integers(0, 8, 200), 8, 8
    if case == "empty_rows":      # ids drawn from every other node only
        return (2 * rng.integers(0, 10, 60), 3 * rng.integers(0, 7, 60),
                23, 25)
    if case == "largest_ids_unused":
        return rng.integers(0, 5, 40), rng.integers(0, 5, 40), 50, 60
    if case == "nnz0":
        return np.zeros(0, np.int64), np.zeros(0, np.int64), 6, 4
    assert case == "no_nodes"
    return np.zeros(0, np.int64), np.zeros(0, np.int64), 0, 0


@pytest.mark.parametrize("case", ["duplicates", "empty_rows",
                                  "largest_ids_unused", "nnz0", "no_nodes"])
def test_from_coo_indptrs_count_edges(case):
    src, dst, n_src, n_dst = _coo_case(case)
    g = from_coo(src, dst, n_src=n_src, n_dst=n_dst)
    indptr_dst, indptr_src = np.asarray(g.indptr_dst), np.asarray(g.indptr_src)
    assert indptr_dst.dtype == indptr_src.dtype == np.int32
    assert len(indptr_dst) == n_dst + 1 and len(indptr_src) == n_src + 1
    for v in range(n_dst):
        assert indptr_dst[v + 1] - indptr_dst[v] == (dst == v).sum()
    for u in range(n_src):
        assert indptr_src[u + 1] - indptr_src[u] == (src == u).sum()
    assert indptr_dst[0] == indptr_src[0] == 0
    # the pointers index the sorted views
    s_dst, s_src = np.asarray(g.dst), np.asarray(g.src)
    perm = np.asarray(g.perm_src)
    for v in range(n_dst):
        assert (s_dst[indptr_dst[v]:indptr_dst[v + 1]] == v).all()
    for u in range(n_src):
        assert (s_src[perm[indptr_src[u]:indptr_src[u + 1]]] == u).all()


def _dst_sorted_case(case):
    """(real src, real dst sorted, n_src, n_dst, n_edges): pads go from
    source n_src-1 into row n_dst-1, which real edges leave free."""
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    if case == "pads":
        n_real = 70
        return (rng.integers(0, 30, n_real),
                np.sort(rng.integers(0, 12, n_real)), 31, 13, 120)
    if case == "duplicates":      # few sources: parallel edges per row
        return (rng.integers(0, 3, 50), np.sort(rng.integers(0, 5, 50)),
                4, 6, 64)
    if case == "no_pads":         # real edges use the last slot and row
        dst = np.sort(rng.integers(0, 9, 40))
        dst[-1] = 8
        src = rng.integers(0, 17, 40)
        src[0] = 16
        return src, dst, 17, 9, 40
    if case == "all_pads":
        return np.zeros(0, np.int64), np.zeros(0, np.int64), 5, 3, 12
    assert case == "empty"
    return np.zeros(0, np.int64), np.zeros(0, np.int64), 0, 0, 0


@pytest.mark.parametrize("case", ["pads", "duplicates", "no_pads",
                                  "all_pads", "empty"])
def test_from_dst_sorted_matches_from_coo(case):
    src, dst, n_src, n_dst, n_edges = _dst_sorted_case(case)
    pad = n_edges - len(src)
    all_src = np.concatenate([src, np.full(pad, n_src - 1)])
    all_dst = np.concatenate([dst, np.full(pad, n_dst - 1)])
    g, (rev_src, rev_dst, rev_eid) = from_dst_sorted(
        src, dst, n_src=n_src, n_dst=n_dst, n_edges=n_edges)
    ref = from_coo(all_src, all_dst, n_src=n_src, n_dst=n_dst)
    assert (g.n_src, g.n_dst, g.n_edges) == (ref.n_src, ref.n_dst,
                                             ref.n_edges)
    for f in ("src", "dst", "eid", "indptr_dst", "indptr_src", "perm_src",
              "eid_inv"):
        got, want = getattr(g, f), getattr(ref, f)
        assert got.dtype == want.dtype, f
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f)
    order = np.argsort(all_src, kind="stable")
    for got, want in ((rev_eid, order), (rev_src, all_src[order]),
                      (rev_dst, all_dst[order])):
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("src,dst,match", [
    ([1, 2], [1, 0], "non-decreasing"),
    ([4, 0], [0, 1], "src ids"),        # a real edge in the pads' source
    ([0, 1], [0, 2], "dst ids"),        # a real edge in the pads' row
    ([0, 1, 2, 3, 0], [0, 0, 1, 1, 1], "n_edges"),
])
def test_from_dst_sorted_rejects_broken_layouts(src, dst, match):
    with pytest.raises(ValueError, match=match):
        from_dst_sorted(np.asarray(src), np.asarray(dst), n_src=5, n_dst=3,
                        n_edges=4)
