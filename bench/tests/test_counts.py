"""FLOP and byte counts against values worked out by hand on a tiny graph:
3 nodes, 5 entries, widths 2 -> 4 -> 1."""
import pytest

from bench import counts

N, NNZ, DIMS = 3, 5, [2, 4, 1]
A_BYTES = NNZ * 8 + (N + 1) * 4          # values, column ids, row pointers


def test_sparse_operand_bytes():
    assert counts.sparse_bytes(N, NNZ) == A_BYTES == 56


def test_gcn_forward():
    # layer 1: 2·3·2·4 + 2·5·4 FLOPs; A, H (3x2), W (2x4), out (3x4)
    # layer 2: 2·3·4·1 + 2·5·1 FLOPs; A, H (3x4), W (4x1), out (3x1)
    got = counts.gcn_forward(N, NNZ, DIMS)
    assert got == {"flops": (48 + 40) + (24 + 10),
                   "bytes": (56 + 4 * (6 + 8 + 12)) + (56 + 4 * (12 + 4 + 3))}


def test_gcn_train_step():
    fwd = counts.gcn_forward(N, NNZ, DIMS)
    # layer 1 dW: 2·5·4 + 2·3·2·4; reads A, G (3x4), H (3x2); writes 2x4
    # layer 2 dW: 2·5·1 + 2·3·4·1; reads A, G (3x1), H (3x4); writes 4x1
    # layer 2 dH: 2·3·1·4 + 2·5·4; reads A, G (3x1), W (4x1); writes 3x4
    got = counts.gcn_train_step(N, NNZ, DIMS)
    assert got["flops"] == fwd["flops"] + 88 + 34 + 64
    assert got["bytes"] == fwd["bytes"] + (56 + 4 * (12 + 6 + 8)) \
        + (56 + 4 * (3 + 12 + 4)) + (56 + 4 * (3 + 4 + 12))


def test_spmm_spmm():
    # two sparse products 2 wide; A once, C (3x2) read, D (3x2) written
    assert counts.spmm_spmm(N, NNZ, 2) == {"flops": 40, "bytes": 56 + 48}


@pytest.mark.parametrize("flops, byts, want", [
    (197e12, 1.0, (1.0, "flops")),
    (1.0, 2 * 819e9, (2.0, "bytes")),
])
def test_least_time_names_the_binding_term(flops, byts, want):
    peak = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    t, binds = counts.least_time_s({"flops": flops, "bytes": byts}, peak)
    assert binds == want[1] and t == pytest.approx(want[0])
