"""Compile every Pallas kernel for a TPU v5e chip, at deployment widths.

No chip is needed: the TPU compiler is installed and compiles for a chip
that is described (``v5e:2x2``) but not attached.  A kernel that Mosaic
refuses — a block that is not (8, 128)-tileable, an unsupported cast or
reshape — fails here, which interpret-mode tests cannot show.  Nothing
runs, so these tests say nothing about results or speed.

Widths: the qwen3-0.6b matmuls (K, N in {1024, 2048, 3072}) at a decode
batch (M = 4) and a prefill chunk (M = 128); flash attention at head_dim
128 over 256 keys; the falcon-mamba-7b selective scan (d_inner 8192,
N 16, chunk 256); and every MobileNet-V2 layer shape of
``configs/siracusa_mnv2.py``, at 8- and 4-bit weights.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

from __future__ import annotations

import os

import pytest

BITS = (8, 4)
MATMULS = [(m, k, n) for m in (4, 128)
           for k, n in ((1024, 2048), (2048, 1024), (1024, 3072),
                        (3072, 1024))]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_tpu(one_chip):
    """compile_tpu(fn, *(shape, dtype)) -> HLO text of the v5e program,
    with the persistent compile cache off (a TPU executable written here
    could not be read back without a chip)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_tpu(fn, *specs):
        args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
                for shape, dtype in specs]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_tpu
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _assert_kernel(hlo: str) -> None:
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("m,k,n", MATMULS)
def test_qmatmul_compiles(compile_tpu, bits, m, k, n):
    import jax.numpy as jnp
    from repro.core.quantize import PAGE_SCALE_BLOCK
    from repro.kernels import qmatmul as qm

    f = 8 // bits
    x, w = ((m, k), jnp.float32), ((n, k // f), jnp.uint8)
    _assert_kernel(compile_tpu(
        lambda x, w, s: qm.qmatmul_f32(x, w, s, bits=bits, k_orig=k),
        x, w, ((n,), jnp.float32)))
    _assert_kernel(compile_tpu(
        lambda x, w, s: qm.qmatmul_f32_blockscale(
            x, w, s, bits=bits, k_orig=k, block=PAGE_SCALE_BLOCK),
        x, w, ((n, k // PAGE_SCALE_BLOCK), jnp.float32)))
    _assert_kernel(compile_tpu(
        lambda x, w, mu, b: qm.qmatmul_int8(x, w, mu, b, bits=bits,
                                            k_orig=k),
        ((m, k), jnp.uint8), w, ((n,), jnp.float32), ((n,), jnp.int32)))


@pytest.mark.parametrize("sq", [256, 1])
def test_flash_attention_compiles(compile_tpu, sq):
    import jax.numpy as jnp
    from repro.kernels.flash_attention import flash_attention

    q, kv = ((16, sq, 128), jnp.float32), ((16, 256, 128), jnp.float32)
    _assert_kernel(compile_tpu(flash_attention, q, kv, kv))


def test_ssm_scan_compiles(compile_tpu):
    import jax.numpy as jnp
    from repro.kernels.ssm_scan import selective_scan_fused

    seq, di, n = ((1, 256, 8192), jnp.float32), 8192, 16
    _assert_kernel(compile_tpu(
        lambda *a: selective_scan_fused(*a, chunk=256),
        seq, seq, ((di, n), jnp.float32), ((1, 256, n), jnp.float32),
        ((1, 256, n), jnp.float32), ((di,), jnp.float32)))


def _mnv2_layers():
    from repro.core.perf_model import mobilenet_v2_jobs
    seen = {}
    for job in mobilenet_v2_jobs(8, 224):
        key = (job.op_kind, job.h, job.w, job.cin, job.cout, job.stride)
        seen.setdefault(key, job)
    return list(seen.values())


@pytest.mark.parametrize("bits", BITS)
@pytest.mark.parametrize("job", _mnv2_layers(), ids=lambda j: (
    f"{j.op_kind}-{j.h}x{j.w}x{j.cin}-{j.cout}-s{j.stride}"))
def test_neureka_conv_compiles(compile_tpu, bits, job):
    """Every distinct MobileNet-V2 layer shape on its N-EUREKA kernel."""
    import jax.numpy as jnp
    from repro.kernels import neureka_conv as nkc

    f = 8 // bits
    x = ((job.h, job.w, job.cin), jnp.uint8)
    if job.op_kind == "dw3x3":
        c = job.cin
        fn = (lambda x, w, mu, b:
              nkc.conv3x3_dw(x, w, mu, b, bits=bits, stride=job.stride))
        w = ((c, -(-9 // f)), jnp.uint8)
    elif job.op_kind == "dense3x3":
        c = job.cout
        fn = (lambda x, w, mu, b:
              nkc.conv3x3_dense(x, w, mu, b, bits=bits, cin=job.cin,
                                stride=job.stride))
        w = ((c, 3, 3, -(-job.cin // f)), jnp.uint8)
    else:
        c = job.cout
        fn = (lambda x, w, mu, b:
              nkc.conv1x1(x, w, mu, b, bits=bits, cin=job.cin,
                          stride=job.stride))
        w = ((c, -(-job.cin // f)), jnp.uint8)
    _assert_kernel(compile_tpu(fn, x, w, ((c,), jnp.float32),
                               ((c,), jnp.int32)))
