"""Property suite for the PQ codec + memmap storage tier (core/pq.py,
core/storage.py ``codec="pq"`` / ``mode="memmap"``).

Every property runs twice: once over a deterministic seed grid (always), and
once hypothesis-fuzzed (when hypothesis is installed, same pattern as
test_fault_properties.py).  Checked invariants:

  * encode→decode reconstruction error is bounded: EXACT (zero) when every
    training row can own a centroid (n <= 256), and never worse than the
    one-centroid-per-subspace baseline otherwise;
  * the roundtrip preserves row count and original dim, for dims divisible
    and NOT divisible by ``m`` (zero-padded tail subspace);
  * LUT scoring is the same linear functional as decode-then-dot;
  * ``payload_rows`` / ``get_many_raw`` honor the pq payload contract;
  * memmap put→get→delete→clear leaves no file, no leaked bytes in
    ``total_bytes()``, and no dangling file handles.
"""
import os

import numpy as np
import pytest

from repro.core.pq import (pq_decode, pq_encode, pq_luts, quantization_error,
                           subspace_split, train_pq)
from repro.core.storage import StorageBackend

pytestmark = pytest.mark.fast

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

SETTINGS = dict(max_examples=40, deadline=None)

# (n, dim, m): dims both divisible and non-divisible by m, n spanning the
# exact-reconstruction regime (n <= 256) and the lossy one
GRID = [(2, 8, 4), (30, 15, 4), (40, 16, 16), (200, 33, 8),
        (300, 16, 8), (500, 24, 24)]


def _emb(n, d, seed):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, d)).astype(np.float32)


# ---------------------------------------------------------------- properties
def check_roundtrip_shape_and_error(n, d, m, seed):
    x = _emb(n, d, seed)
    cb = train_pq(x, m=m, iters=6, seed=seed)
    codes = pq_encode(cb, x)
    rec = pq_decode(cb, codes)
    assert codes.shape == (n, cb.m) and codes.dtype == np.uint8
    assert rec.shape == (n, d) and rec.dtype == np.float32
    err = quantization_error(cb, x)
    assert np.all(np.isfinite(err)) and np.all(err >= 0)
    if n <= 256:
        # every training row can own a centroid: exact reconstruction
        assert float(err.max()) <= 1e-6
    else:
        # never worse than quantizing each subspace to its single mean
        sub = subspace_split(x, cb)
        k1 = float(np.sum((sub - sub.mean(0, keepdims=True)) ** 2)) / n
        assert float(err.mean()) <= k1 + 1e-6


def check_lut_matches_decode_dot(n, d, m, seed):
    x = _emb(n, d, seed)
    q = _emb(3, d, seed + 1)
    cb = train_pq(x, m=m, iters=6, seed=seed)
    codes = pq_encode(cb, x)
    luts = pq_luts(cb, q)
    assert luts.shape == (3, cb.m, 256)
    s_lut = np.stack([luts[i, np.arange(cb.m), codes].sum(axis=1)
                      for i in range(3)])
    s_dec = q @ pq_decode(cb, codes).T
    scale = max(1.0, float(np.abs(s_dec).max()))
    assert np.abs(s_lut - s_dec).max() <= 1e-4 * scale


def check_payload_contract(n, d, m, seed):
    s = StorageBackend("memory", codec="pq", pq_m=m)
    x = _emb(n, d, seed)
    s.put(7, x)
    raw = s.get_many_raw([7])[0]
    assert s.payload_rows(raw) == n
    assert set(raw) >= {"codes", "cbv"}
    assert raw["codes"].shape == (n, s.pq.m) and raw["codes"].dtype == np.uint8
    assert int(np.asarray(raw["cbv"]).reshape(-1)[0]) == s.pq.version
    # the raw codes decode to the same rows get() returns
    assert np.array_equal(s.get(7), pq_decode(s.pq, raw["codes"]))


def check_memmap_lifecycle(tmpdir, n, d, m, seed):
    s = StorageBackend("memmap", root=str(tmpdir), codec="pq", pq_m=m)
    x = _emb(n, d, seed)
    nbytes = s.put(3, x)
    assert s.total_bytes() == nbytes == s.stored_bytes(3)
    raw = s.get_many_raw([3])[0]
    assert isinstance(raw["codes"], np.memmap)       # disk-native: no copy
    before = len(os.listdir("/proc/self/fd"))
    for _ in range(8):                               # handle-leak probe
        got = s.get_many_raw([3])[0]["codes"]
        assert got.shape == (n, s.pq.m)
        del got
    assert len(os.listdir("/proc/self/fd")) <= before + 1
    s.delete(3)
    assert 3 not in s and s.total_bytes() == 0
    s.put(4, x)
    s.clear()
    assert s.total_bytes() == 0
    left = [f for f in os.listdir(str(tmpdir)) if f.endswith(".npz")
            and not f.startswith("pq_codebook")]
    assert left == []


# ------------------------------------------------- deterministic grid (always)
@pytest.mark.parametrize("n,d,m", GRID)
def test_roundtrip_shape_and_error(n, d, m):
    check_roundtrip_shape_and_error(n, d, m, seed=n + d + m)


@pytest.mark.parametrize("n,d,m", GRID)
def test_lut_matches_decode_dot(n, d, m):
    check_lut_matches_decode_dot(n, d, m, seed=n + d + m)


@pytest.mark.parametrize("n,d,m", [(5, 8, 4), (30, 15, 4), (64, 33, 8)])
def test_payload_contract(n, d, m):
    check_payload_contract(n, d, m, seed=n + d + m)


@pytest.mark.parametrize("n,d,m", [(5, 8, 4), (30, 15, 4), (64, 33, 8)])
def test_memmap_lifecycle(tmp_path, n, d, m):
    check_memmap_lifecycle(tmp_path, n, d, m, seed=n + d + m)


# ------------------------------------------------------ hypothesis fuzz layer
if HAVE_HYPOTHESIS:
    @settings(**SETTINGS)
    @given(n=st.integers(2, 300), d=st.sampled_from([8, 15, 16, 33]),
           m=st.sampled_from([4, 8, 16]), seed=st.integers(0, 10_000))
    def test_roundtrip_shape_and_error_fuzz(n, d, m, seed):
        check_roundtrip_shape_and_error(n, d, m, seed)

    @settings(**SETTINGS)
    @given(n=st.integers(2, 120), d=st.sampled_from([8, 15, 33]),
           m=st.sampled_from([4, 8]), seed=st.integers(0, 10_000))
    def test_lut_matches_decode_dot_fuzz(n, d, m, seed):
        check_lut_matches_decode_dot(n, d, m, seed)

    @settings(**SETTINGS)
    @given(n=st.integers(2, 64), d=st.sampled_from([8, 15, 33]),
           m=st.sampled_from([4, 8]), seed=st.integers(0, 10_000))
    def test_payload_contract_fuzz(n, d, m, seed):
        check_payload_contract(n, d, m, seed)

    @settings(max_examples=15, deadline=None)
    @given(n=st.integers(2, 64), d=st.sampled_from([8, 15, 33]),
           m=st.sampled_from([4, 8]), seed=st.integers(0, 10_000))
    def test_memmap_lifecycle_fuzz(n, d, m, seed):
        import tempfile
        with tempfile.TemporaryDirectory() as td:
            check_memmap_lifecycle(td, n, d, m, seed)
