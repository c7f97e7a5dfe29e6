"""Graph substrate: the 1-D and 2-D edge partitioners."""
import numpy as np
import pytest

from repro.graph import web_graph
from repro.graph.partition import partition_1d, partition_2d


class TestPartition:
    @pytest.mark.parametrize("R", [2, 4, 8])
    def test_1d_covers_all_edges(self, R):
        g = web_graph(200, 1600, dangling_frac=0.1, seed=4)
        p = partition_1d(g, R)
        total = int(np.sum(p.src != g.n))
        assert total == g.m
        # dst-locality: every real edge's global dst lies in its block
        for r in range(R):
            mask = p.src[r] != g.n
            dsts = p.dst_local[r][mask] + r * p.nr
            assert dsts.min() >= r * p.nr and dsts.max() < (r + 1) * p.nr

    @pytest.mark.parametrize("R,C", [(2, 2), (4, 2), (2, 4)])
    def test_2d_roundtrip_and_coverage(self, R, C):
        g = web_graph(300, 2400, dangling_frac=0.15, seed=5)
        p = partition_2d(g, R, C)
        # permutation is a bijection
        assert np.array_equal(np.sort(p.perm), np.arange(p.n_pad))
        # layout round-trip
        x = np.random.default_rng(0).random(g.n)
        col = p.to_col_layout(x)
        np.testing.assert_allclose(p.from_col_layout(col), x)
        # edge coverage
        total = int(np.sum(p.src_local != p.nc))
        assert total == g.m

    def test_2d_block_locality(self):
        """Edge in block (i,j): dst in row-block i, src in col-block j."""
        g = web_graph(160, 1000, seed=6)
        R, C = 2, 2
        p = partition_2d(g, R, C)
        for i in range(R):
            for j in range(C):
                mask = p.src_local[i, j] != p.nc
                if not mask.any():
                    continue
                # dst_local indexes into row block i
                assert p.dst_local[i, j][mask].max() < p.nr
                # src_local indexes into column block j (strided layout)
                assert p.src_local[i, j][mask].max() < p.nc
