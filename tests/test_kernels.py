"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode.

Every kernel runs through pl.pallas_call with its real BlockSpec grid in
interpret mode on the CPU and must match its ref.py oracle to tight
tolerance.  That the kernels compile for the chip is checked apart, in
tests/test_tpu_compile.py.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_prefill.kernel import flash_prefill
from repro.kernels.flash_prefill.ref import dense_ref
from repro.kernels.kv_pull.kernel import kv_pull, kv_pull_dequant, kv_pull_runs
from repro.kernels.kv_pull.ref import (
    kv_pull_dequant_ref,
    kv_pull_ref,
    kv_pull_runs_ref,
)
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref

RNG = np.random.default_rng(42)

def arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, dtype)


TOL = {jnp.float32: 2e-4, jnp.bfloat16: 2e-2}


class TestPagedAttention:
    @pytest.mark.parametrize("b,h,g,d,per,bs", [
        (2, 4, 2, 64, 4, 32),
        (3, 8, 1, 128, 3, 32),   # MQA, granite-style
        (1, 8, 8, 64, 5, 16),    # MHA
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, b, h, g, d, per, bs, dtype):
        q = arr((b, h, d), dtype)
        kp, vp = arr((b, per, bs, g, d), dtype), arr((b, per, bs, g, d), dtype)
        tbl = jnp.broadcast_to(jnp.arange(per, dtype=jnp.int32)[None], (b, per))
        ctx = jnp.asarray(RNG.integers(1, per * bs, b), jnp.int32)
        ref = paged_attention_ref(q, kp, vp, tbl, ctx)
        out = paged_attention(q, kp, vp, tbl, ctx, interpret=True)
        tol = TOL[dtype]
        np.testing.assert_allclose(
            out.astype(jnp.float32), ref.astype(jnp.float32), rtol=tol, atol=tol)

    def test_permuted_block_table(self):
        """Pages stored out of order; the table restores the sequence."""
        b, h, g, d, per, bs = 1, 4, 2, 32, 4, 16
        q = arr((b, h, d))
        kp, vp = arr((b, per, bs, g, d)), arr((b, per, bs, g, d))
        perm = jnp.asarray([[2, 0, 3, 1]], jnp.int32)
        ctx = jnp.asarray([per * bs], jnp.int32)
        ref = paged_attention_ref(q, kp, vp, perm, ctx)
        out = paged_attention(q, kp, vp, perm, ctx, interpret=True)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    def test_single_token_context(self):
        b, h, g, d, per, bs = 2, 2, 1, 32, 2, 16
        q = arr((b, h, d))
        kp, vp = arr((b, per, bs, g, d)), arr((b, per, bs, g, d))
        tbl = jnp.broadcast_to(jnp.arange(per, dtype=jnp.int32)[None], (b, per))
        ctx = jnp.ones((b,), jnp.int32)
        ref = paged_attention_ref(q, kp, vp, tbl, ctx)
        out = paged_attention(q, kp, vp, tbl, ctx, interpret=True)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


class TestKVPull:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
    def test_txn_list(self, dtype):
        src = jnp.asarray(RNG.integers(-100, 100, (12, 16, 2, 32)), dtype)
        dst = jnp.asarray(RNG.integers(-100, 100, (10, 16, 2, 32)), dtype)
        sid = jnp.asarray([0, 5, 11, 3], jnp.int32)
        did = jnp.asarray([9, 1, 4, 0], jnp.int32)
        ref = kv_pull_ref(src, dst, sid, did)
        out = kv_pull(src, dst, sid, did, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("run_len", [2, 4])
    def test_coalesced_runs(self, run_len):
        src = arr((16, 8, 2, 64))
        dst = arr((16, 8, 2, 64))
        ss = jnp.asarray([0, 2], jnp.int32)
        ds = jnp.asarray([3, 1], jnp.int32)
        ref = kv_pull_runs_ref(src, dst, ss, ds, run_len=run_len)
        out = kv_pull_runs(src, dst, ss, ds, run_len=run_len, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("dst_dtype", [jnp.float32, jnp.bfloat16])
    def test_dequant_txn_list(self, dst_dtype):
        """Quantized delta pull: int8 wire pages land dequantized with
        their per-transaction scale (ReadTxn.qscale on device)."""
        src = jnp.asarray(RNG.integers(-127, 128, (12, 16, 2, 32)), jnp.int8)
        dst = jnp.asarray(RNG.standard_normal((10, 16, 2, 32)), dst_dtype)
        sid = jnp.asarray([0, 5, 11, 3], jnp.int32)
        did = jnp.asarray([9, 1, 4, 0], jnp.int32)
        scales = jnp.asarray([0.013, 1.0, 0.5, 0.0021], jnp.float32)
        ref = kv_pull_dequant_ref(src, dst, sid, did, scales)
        out = kv_pull_dequant(src, dst, sid, did, scales, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_dequant_untouched_pages_survive(self):
        """Destination is aliased (RDMA-write semantics): pages no
        transaction names keep their contents bit-for-bit."""
        src = jnp.asarray(RNG.integers(-127, 128, (4, 8, 2, 16)), jnp.int8)
        dst = jnp.asarray(RNG.standard_normal((6, 8, 2, 16)), jnp.float32)
        keep = np.array(dst)
        sid, did = jnp.asarray([2], jnp.int32), jnp.asarray([3], jnp.int32)
        out = kv_pull_dequant(src, dst, sid, did,
                              jnp.asarray([0.25], jnp.float32),
                              interpret=True)
        out = np.asarray(out)
        np.testing.assert_array_equal(out[[0, 1, 2, 4, 5]],
                                      keep[[0, 1, 2, 4, 5]])
        np.testing.assert_allclose(out[3], src[2].astype(np.float32) * 0.25)

    def test_dequant_roundtrip_bound(self):
        """Symmetric int8 round-trip of bf16-scale data stays within the
        documented tolerance: |err| <= max(|x|)/127 per page."""
        x = np.asarray(RNG.standard_normal((3, 8, 2, 16)), np.float32)
        scales = np.abs(x).reshape(3, -1).max(axis=1) / 127.0
        q = np.clip(np.round(x / scales[:, None, None, None]),
                    -127, 127).astype(np.int8)
        dst = jnp.zeros((3, 8, 2, 16), jnp.float32)
        ids = jnp.arange(3, dtype=jnp.int32)
        out = kv_pull_dequant(jnp.asarray(q), dst, ids, ids,
                              jnp.asarray(scales), interpret=True)
        err = np.max(np.abs(np.asarray(out) - x), axis=(1, 2, 3))
        assert (err <= np.abs(x).reshape(3, -1).max(axis=1) / 127.0
                + 1e-7).all()

    def test_full_request_transfer_shape(self):
        """Paper-scale mini: 1024-block request pulled in 8-block runs."""
        src = arr((64, 16, 2, 32))
        dst = jnp.zeros((64, 16, 2, 32), jnp.float32)
        ss = jnp.arange(8, dtype=jnp.int32)
        ds = jnp.arange(8, dtype=jnp.int32)
        out = kv_pull_runs(src, dst, ss, ds, run_len=8, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(src))


class TestFlashPrefill:
    @pytest.mark.parametrize("s,h,g,d,bq", [
        (256, 4, 2, 64, 64),
        (128, 8, 8, 32, 32),
        (256, 6, 1, 128, 128),  # MQA
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal(self, s, h, g, d, bq, dtype):
        q, k, v = arr((2, s, h, d), dtype), arr((2, s, g, d), dtype), arr((2, s, g, d), dtype)
        ref = dense_ref(q, k, v, causal=True)
        out = flash_prefill(q, k, v, causal=True, block_q=bq, block_k=bq, interpret=True)
        tol = TOL[dtype]
        np.testing.assert_allclose(
            out.astype(jnp.float32), ref.astype(jnp.float32), rtol=tol, atol=tol)

    def test_sliding_window_and_prefix(self):
        s, h, g, d = 256, 4, 2, 32
        q, k, v = arr((1, s, h, d)), arr((1, s, g, d)), arr((1, s, g, d))
        ref = dense_ref(q, k, v, causal=True, sliding_window=64, prefix_len=16)
        out = flash_prefill(q, k, v, causal=True, sliding_window=64, prefix_len=16,
                            block_q=32, block_k=32, interpret=True)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    def test_non_causal(self):
        s, h, g, d = 128, 4, 4, 32
        q, k, v = arr((1, s, h, d)), arr((1, s, g, d)), arr((1, s, g, d))
        ref = dense_ref(q, k, v, causal=False)
        out = flash_prefill(q, k, v, causal=False, block_q=64, block_k=64, interpret=True)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


class TestSSDScan:
    @pytest.mark.parametrize("s,nh,hd,ns,chunk", [
        (128, 4, 32, 16, 32),
        (64, 2, 64, 128, 64),   # mamba2-780m-like dstate
        (96, 50, 64, 16, 32),   # hymba-like head count
    ])
    def test_matches_ref(self, s, nh, hd, ns, chunk):
        b = 2
        x = arr((b, s, nh, hd), scale=0.5)
        dt = jnp.asarray(np.abs(RNG.standard_normal((b, s, nh))) * 0.1 + 0.01, jnp.float32)
        a = -jnp.asarray(np.abs(RNG.standard_normal(nh)) + 0.5, jnp.float32)
        B = arr((b, s, ns), scale=0.3)
        C = arr((b, s, ns), scale=0.3)
        d_skip = arr((nh,))
        y_ref, st_ref = ssd_scan_ref(x, dt, a, B, C, d_skip, chunk=chunk)
        y, st = ssd_scan(x, dt, a, B, C, d_skip, chunk=chunk, interpret=True)
        np.testing.assert_allclose(y, y_ref, rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(st, st_ref, rtol=1e-3, atol=1e-3)

    def test_decay_extremes_stable(self):
        """Very small dt (state persists) and large dt (state forgets)."""
        b, s, nh, hd, ns = 1, 64, 2, 16, 8
        x = arr((b, s, nh, hd), scale=0.5)
        B, C = arr((b, s, ns), scale=0.3), arr((b, s, ns), scale=0.3)
        a = jnp.asarray([-0.01, -8.0], jnp.float32)
        d_skip = jnp.zeros((nh,), jnp.float32)
        for dt_scale in (1e-3, 5.0):
            dt = jnp.full((b, s, nh), dt_scale, jnp.float32)
            y, st = ssd_scan(x, dt, a, B, C, d_skip, chunk=16, interpret=True)
            assert bool(jnp.all(jnp.isfinite(y))) and bool(jnp.all(jnp.isfinite(st)))
