"""Reed-Solomon shred coding: GF(2^8) algebra, the MXU bit-matmul
equivalence vs a scalar GF oracle, and erasure recovery from every
pattern class."""

import numpy as np
import pytest

from firedancer_tpu.ballet import gf256 as GF
from firedancer_tpu.ops import reedsol as RS

pytestmark = pytest.mark.slow


def test_gf_field_axioms():
    rng = np.random.default_rng(0)
    for _ in range(200):
        a, b, c = (int(x) for x in rng.integers(1, 256, 3))
        assert GF.mul(a, GF.inv(a)) == 1
        assert GF.mul(a, b) == GF.mul(b, a)
        assert GF.mul(a, GF.mul(b, c)) == GF.mul(GF.mul(a, b), c)
        assert GF.div(GF.mul(a, b), b) == a
    assert GF.mul(0, 123) == 0
    assert GF.mul(2, 0x80) == (0x100 ^ GF.POLY) & 0xFF  # poly reduction


def test_mat_inv_roundtrip():
    rng = np.random.default_rng(1)
    for n in (1, 3, 8):
        while True:
            A = rng.integers(0, 256, (n, n)).astype(np.uint8)
            try:
                Ainv = GF.mat_inv(A)
                break
            except ValueError:
                continue
        eye = GF.mat_mul(A, Ainv)
        assert (eye == np.eye(n, dtype=np.uint8)).all()


def test_code_matrix_systematic():
    m = GF.code_matrix(4, 7)
    assert (m[:4] == np.eye(4, dtype=np.uint8)).all()
    assert m.shape == (7, 4)
    # any 4 rows are invertible (MDS property of the construction)
    import itertools

    for rows in itertools.combinations(range(7), 4):
        GF.mat_inv(m[list(rows)])  # must not raise


def test_bitmatrix_equals_gf_mul():
    rng = np.random.default_rng(2)
    for _ in range(50):
        c, x = (int(v) for v in rng.integers(0, 256, 2))
        M = GF.mul_bitmatrix(c)
        xbits = np.array([(x >> j) & 1 for j in range(8)])
        ybits = (M @ xbits) % 2
        y = sum(int(b) << i for i, b in enumerate(ybits))
        assert y == GF.mul(c, x)


def _oracle_encode(data, parity_cnt):
    M = GF.parity_matrix(len(data), parity_cnt)
    P, N = parity_cnt, data.shape[1]
    out = np.zeros((P, N), dtype=np.uint8)
    for p in range(P):
        for d in range(len(data)):
            c = int(M[p, d])
            if c:
                lut = np.array([GF.mul(c, v) for v in range(256)], np.uint8)
                out[p] ^= lut[data[d]]
    return out


@pytest.mark.parametrize("D,P", [(1, 1), (4, 3), (8, 8), (32, 32)])
def test_encode_matches_oracle(D, P):
    rng = np.random.default_rng(D * 100 + P)
    N = 64
    data = rng.integers(0, 256, (D, N)).astype(np.uint8)
    want = _oracle_encode(data, P)
    # both dispatch paths must agree with the oracle (auto-size picks
    # host here; device=True forces the MXU bit-matrix kernel)
    assert (RS.encode(data, P) == want).all()
    assert (RS.encode(data, P, device=True) == want).all()


@pytest.mark.parametrize(
    "lost",
    [
        [0],  # lose a data shred
        [4, 5],  # lose parity only
        [0, 1, 5],  # mixed
        [0, 1, 2, 3],  # all data lost, recover purely from parity
    ],
)
@pytest.mark.parametrize("device", [None, True], ids=["host", "device"])
def test_recover(lost, device):
    D, P, N = 4, 4, 48
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (D, N)).astype(np.uint8)
    parity = RS.encode(data, P)
    shreds = np.concatenate([data, parity])
    present = np.ones(D + P, dtype=bool)
    for i in lost:
        present[i] = False
        shreds[i] = 0xAA  # garbage
    # None = auto by size: one small set recovers on the host (the
    # store tile's case); True forces the device matmul
    out = RS.recover(shreds, present, D, device=device)
    assert out is not None
    assert (out == data).all()


def test_recover_partial_fails():
    D, P, N = 4, 2, 16
    data = np.zeros((D, N), np.uint8)
    parity = RS.encode(data, P)
    shreds = np.concatenate([data, parity])
    present = np.zeros(D + P, dtype=bool)
    present[:3] = True  # only 3 of 4 needed survive
    assert RS.recover(shreds, present, D) is None
