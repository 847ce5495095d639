#!/usr/bin/env python3
"""Chip smoke test: the serving main path on a TPU, at full width.

    python3 chip_smoke.py              # one chip: every phase below
    python3 chip_smoke.py --chips 4    # four chips: mesh-sharded paging only

Everything runs in this one process, so no second process ever wants the
chip.  On one chip, qwen3-0.6b at its published widths (28 layers,
d_model 1024, 16/8 heads, head_dim 128, d_ff 3072, vocab 151936; random
weights from a seed, int8 packed):

  1. device guard   — no TPU is an error, never a fallback to the CPU;
  2. resident serve — ``repro.launch.serve`` with the uniform l1mram plan;
  3. forward check  — the engine's prefill logits, and the first decode
     step's, against ``transformer.forward`` of the same packed params;
  4. paged serve    — the launcher with a ``--budget-mb`` that keeps about
     half of the packed matmul bytes resident; its own verify legs must
     pass (paged vs resident and async vs sync, bit-exact);
  5. kernels        — every Pallas kernel of ``repro.kernels`` in pallas
     mode at deployment widths against its pure-jnp oracle.

``--chips 4`` runs only ``serve --mesh 4`` over paged weights, with the
single-device paged run the launcher compares it with, and checks that
each device link holds its pages on its own chip.

Phase lines report wall and compile seconds and the device-memory peak,
labelled with the device.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; a failed phase
raises before it is printed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.perf_model import mobilenet_v2_jobs  # noqa: E402
from repro.core.placement import PlacementPlan, packed_sizes  # noqa: E402
from repro.core.quantize import PAGE_SCALE_BLOCK, quantize_blockwise  # noqa: E402
from repro.core import packing  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.kernels.ssm_scan import selective_scan_fused  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import transformer as tfm  # noqa: E402
from repro.models.ssm import selective_scan  # noqa: E402
from repro.parallel.sharding import freeze_for_serving  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402

ARCH = "qwen3-0.6b"
OUT_DIR = os.path.join(ROOT, "chiprun_out")
# Float kernels and oracles may each feed the MXU bf16-rounded operands
# (TPU default matmul precision): allow 8 bf16 epsilons of the output's
# magnitude.  Integer kernels must match exactly.
FLOAT_TOL = 8 * 2.0 ** -8


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Run:
    """Launcher knobs shared by every serve phase."""
    smoke: bool = False
    requests: int = 8
    max_new: int = 16
    slots: int = 4
    max_len: int = 256
    bits: int = 8

    def argv(self, *extra: str) -> list:
        argv = ["--arch", ARCH, "--bits", str(self.bits),
                "--requests", str(self.requests),
                "--max-new", str(self.max_new), "--slots", str(self.slots),
                "--max-len", str(self.max_len)]
        return argv + (["--smoke"] if self.smoke else []) + list(extra)

    def config(self):
        cfg = get_config(ARCH)
        return cfg.smoke() if self.smoke else cfg


class Meter:
    """Wall time, backend-compile seconds and persistent-cache hits of one
    phase, from JAX's monitoring events."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def __enter__(self) -> "Meter":
        return self

    def __exit__(self, *exc) -> bool:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)
        return False

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def phase(self, name: str, fn, *args, **kw):
        c0, h0, t0 = self.compile_s, self.cache_hits, time.perf_counter()
        out = fn(*args, **kw)
        wall = time.perf_counter() - t0
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"phase {name}: {wall:.3f} s wall, "
              f"{self.compile_s - c0:.3f} s compiling "
              f"({self.cache_hits - h0} persistent-cache hits), "
              f"peak device memory "
              f"{'not reported' if peak is None else f'{peak} B'} "
              f"[{dev.platform} {dev.device_kind}]", flush=True)
        return out


def device_guard(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform "
                         f"{d.platform!r}); this script runs only on a chip")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, found {len(devs)}")
    return dict(platform=d.platform, kind=d.device_kind, count=len(devs))


# -- serving phases ----------------------------------------------------------

def _check_served(done, run: Run) -> None:
    check(len(done) == run.requests,
          f"served {len(done)} of {run.requests} requests")
    for r in done:
        check(r.done and not r.truncated
              and len(r.generated) == run.max_new,
              f"request {r.uid}: done={r.done} truncated={r.truncated} "
              f"{len(r.generated)}/{run.max_new} tokens")


def phase_resident(run: Run) -> None:
    _check_served(serve.main(run.argv("--scenario", "l1mram")), run)


def build_packed(run: Run):
    """The launcher's model: same config, seed and packing."""
    cfg = run.config()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, freeze_for_serving(params, bits=run.bits)


def phase_forward(run: Run, cfg, packed, prompt_len: int = 12) -> None:
    """Prefill logits and the first decode step's logits of the serving
    engine vs the full forward over the same tokens.  The engine reads
    and writes its KV cache on this path; the forward has no cache."""
    plan = PlacementPlan.uniform("l1mram", bits=run.bits)
    eng = ServingEngine(cfg, packed, batch_slots=run.slots,
                        max_len=run.max_len, plan=plan)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
    bucket = 1 << (prompt_len - 1).bit_length()
    tokens = np.zeros((run.slots, bucket), np.int32)
    tokens[:, :prompt_len] = prompt
    zeros = jnp.zeros((run.slots,), jnp.int32)
    prefill = eng._prefill_for_bucket(bucket, True, bucket)
    logits, cache = prefill(eng.params, jnp.asarray(tokens), eng.cache,
                            zeros, zeros)
    got_pre = np.asarray(logits[0, :prompt_len], np.float32)

    nxt = int(np.argmax(got_pre[-1]))
    dec_tok = np.zeros((run.slots, 1), np.int32)
    dec_tok[0, 0] = nxt
    pos = np.full((run.slots,), run.max_len - 1, np.int32)
    pos[0] = prompt_len
    logits, _ = eng._decode(eng.params, jnp.asarray(dec_tok), cache,
                            jnp.asarray(pos))
    got_dec = np.asarray(logits[0, -1], np.float32)

    seq = np.concatenate([prompt, [nxt]]).astype(np.int32)
    want = np.asarray(tfm.forward(packed, jnp.asarray(seq)[None], cfg,
                                  engine=plan)[0], np.float32)
    for name, got, exp in (("prefill", got_pre, want[:prompt_len]),
                           ("decode", got_dec, want[prompt_len])):
        err = float(np.max(np.abs(got - exp)))
        tol = FLOAT_TOL * float(np.max(np.abs(exp)))
        print(f"forward check {name}: max |engine - forward| {err!r} "
              f"tol {tol!r}", flush=True)
        check(np.isfinite(got).all() and err <= tol,
              f"{name} logits differ from the full forward: {err} > {tol}")


def _metrics_path(name: str) -> str:
    os.makedirs(OUT_DIR, exist_ok=True)
    return os.path.join(OUT_DIR, f"chip_smoke_{name}.json")


def phase_paged(run: Run, budget_mb: float) -> None:
    """Paged serve; the launcher exits nonzero if its paged-vs-resident or
    async-vs-sync verify leg is not bit-exact."""
    path = _metrics_path("paged")
    done = serve.main(run.argv("--budget-mb", repr(budget_mb),
                               "--metrics-json", path))
    _check_served(done, run)
    with open(path) as f:
        paging = json.load(f)["paging"]
    check(paging["n_pages"] > 0 and paging["swap_count"] > 0,
          f"nothing paged under --budget-mb {budget_mb}: {paging}")


def phase_mesh(run: Run, budget_mb: float, n: int) -> None:
    """``--mesh n`` sharded paging vs the single-device paged run."""
    path = _metrics_path("mesh")
    done = serve.main(run.argv("--budget-mb", repr(budget_mb),
                               "--mesh", str(n), "--metrics-json", path))
    _check_served(done, run)
    with open(path) as f:
        mesh = json.load(f)["mesh"]
    led, single = mesh["ledger"], mesh["single_device"]
    links = [d["bytes_streamed_wire"] for d in led["per_device"]]
    print(f"mesh {mesh['shape']}: per-link wire {links} B, global "
          f"{led['bytes_streamed_wire']} B, single-device "
          f"{single['bytes_streamed_wire']} B", flush=True)
    check(mesh["n_devices"] == n and mesh["sharded_params"] > 0,
          f"mesh did not shard across {n} devices: {mesh}")
    check(mesh["bit_exact"] and mesh["predicted_ok"] and mesh["ledger_ok"],
          "mesh run not bit-exact with the single-device run, or its "
          "ledger broke")
    check(led["bytes_streamed_wire"] == single["bytes_streamed_wire"],
          "global wire bytes differ from the single-device run")
    check(all(w < single["bytes_streamed_wire"] for w in links),
          "a device link moved as much as the single device did")


def phase_link_placement(packed, n: int) -> None:
    """Each ShardedPagedStore link must fetch onto its own device."""
    from repro.core.paging import ShardedPagedStore, packed_tree_store
    from repro.launch.mesh import make_test_mesh
    store = packed_tree_store(packed, None)
    mesh = make_test_mesh((1, n), ("data", "model"))
    page_bytes = max(p.nbytes_packed for p in store.params.values())
    with ShardedPagedStore(store, page_bytes, mesh) as sps:
        check(len(set(sps.devices)) == n, f"links share devices: "
              f"{sps.devices}")
        for sub in sps.stores:
            with sub.begin_pass() as ps:
                fetched = ps.fence()
            placed = {d for p in fetched.values()
                      for a in (p.packed, p.scale) for d in a.devices()}
            print(f"link {sub.name}: {len(fetched)} params on {placed}",
                  flush=True)
            check(placed == {sub.device},
                  f"link {sub.name} put pages on {placed}, not on its "
                  f"own device {sub.device}")


# -- kernels -----------------------------------------------------------------

@dataclasses.dataclass
class KernelCase:
    name: str
    kernel: object      # () -> array, the Pallas kernel
    oracle: object      # () -> array, its pure-jnp reference
    exact: bool         # integer kernel: must match bit for bit


def _rand(rng, shape, dtype=jnp.float32, lo=None, hi=None):
    if lo is not None:
        return jnp.asarray(rng.integers(lo, hi, shape), dtype)
    return jnp.asarray(rng.normal(size=shape), dtype)


def _requant(rng, cout: int, k_red: int):
    """Per-channel NORMQUANT params that keep outputs inside [0, 255]."""
    mult = rng.uniform(0.5, 1.5, cout) * 40.0 / (128.0 * 40.0 * k_red ** 0.5)
    return (jnp.asarray(mult, jnp.float32),
            jnp.asarray(np.full(cout, 128), jnp.int32))


def kernel_cases(mode: str, full: bool = True):
    """One case per kernel and width: deployment widths when ``full``,
    tiny ones otherwise (interpret-mode tests)."""
    rng = np.random.default_rng(0)
    cases = []
    mats = ([(4, 1024, 2048), (128, 1024, 2048), (4, 2048, 1024),
             (128, 2048, 1024), (4, 1024, 3072), (128, 1024, 3072),
             (4, 3072, 1024), (128, 3072, 1024)] if full
            else [(4, 64, 32), (8, 96, 16)])
    for bits in (8, 4):
        for m, k, n in mats:
            x = _rand(rng, (m, k))
            w = _rand(rng, (n, k))
            packed, scale = ops.prep_linear(w, bits)
            tag = f"b{bits} {m}x{k}x{n}"
            cases.append(KernelCase(
                f"qmatmul_f32 {tag}",
                lambda x=x, p=packed, s=scale, b=bits, k=k:
                    ops.quant_matmul(x, p, s, bits=b, k_orig=k, mode=mode),
                lambda x=x, p=packed, s=scale, b=bits, k=k:
                    ref.qmatmul_f32(x, p, s, bits=b, k_orig=k), False))
            lv, bs = quantize_blockwise(np.asarray(w), bits)
            bp = packing.pack(jnp.asarray(lv), bits)
            bs = jnp.asarray(bs)
            cases.append(KernelCase(
                f"qmatmul_f32_blockscale {tag}",
                lambda x=x, p=bp, s=bs, b=bits, k=k:
                    ops.quant_matmul_blockscale(
                        x, p, s, bits=b, k_orig=k, block=PAGE_SCALE_BLOCK,
                        mode=mode),
                lambda x=x, p=bp, s=bs, b=bits, k=k:
                    ref.qmatmul_f32_blockscale(
                        x, p, s, bits=b, k_orig=k, block=PAGE_SCALE_BLOCK),
                False))
            xq = _rand(rng, (m, k), jnp.uint8, 0, 256)
            mult, bias = _requant(rng, n, k)
            cases.append(KernelCase(
                f"qmatmul_int8 {tag}",
                lambda x=xq, p=packed, mu=mult, bi=bias, b=bits, k=k:
                    ops.quant_matmul_int8(x, p, mu, bi, bits=b, k_orig=k,
                                          mode=mode),
                lambda x=xq, p=packed, mu=mult, bi=bias, b=bits, k=k:
                    ref.qmatmul_int8(x, p, mu, bi, bits=b, k_orig=k),
                True))

    heads, seq, hd = (16, 256, 128) if full else (2, 16, 16)
    for sq in (seq, 1):
        q = _rand(rng, (heads, sq, hd))
        kk = _rand(rng, (heads, seq, hd))
        v = _rand(rng, (heads, seq, hd))
        cases.append(KernelCase(
            f"flash_attention {heads}x{sq}x{seq} d{hd}",
            lambda q=q, k=kk, v=v: ops.attention(q, k, v, mode=mode),
            lambda q=q, k=kk, v=v: ref.flash_attention(q, k, v), False))

    di, n, chunk, s = (8192, 16, 256, 256) if full else (16, 4, 8, 16)
    ssm = (_rand(rng, (1, s, di)),
           jnp.asarray(rng.uniform(0.001, 0.1, (1, s, di)), jnp.float32),
           -jnp.asarray(rng.uniform(0.5, 2.0, (di, n)), jnp.float32),
           _rand(rng, (1, s, n)), _rand(rng, (1, s, n)), _rand(rng, (di,)))
    cases.append(KernelCase(
        f"ssm_scan d_inner {di} N {n} chunk {chunk}",
        lambda a=ssm: selective_scan_fused(*a, chunk=chunk,
                                           interpret=mode == "interpret"),
        lambda a=ssm: selective_scan(*a, chunk=chunk)[0], False))

    jobs = (mobilenet_v2_jobs(8, 224) if full else
            [j for j in mobilenet_v2_jobs(8, 32)
             if j.name in ("conv0", "b1.dw", "b1.pw_proj")])
    for bits in (8, 4):
        seen = set()
        for job in jobs:
            key = (job.op_kind, job.h, job.w, job.cin, job.cout, job.stride)
            if key in seen:
                continue
            seen.add(key)
            x = _rand(rng, (job.h, job.w, job.cin), jnp.uint8, 0, 256)
            if job.op_kind == "dense3x3":
                packed, _ = ops.prep_conv3x3(
                    _rand(rng, (job.cout, 3, 3, job.cin)), bits)
                k_red, cout = 9 * job.cin, job.cout
            elif job.op_kind == "dw3x3":
                packed, _ = ops.prep_dw3x3(_rand(rng, (job.cin, 3, 3)), bits)
                k_red, cout = 9, job.cin
            else:
                packed, _ = ops.prep_linear(
                    _rand(rng, (job.cout, job.cin)), bits)
                k_red, cout = job.cin, job.cout
            mult, bias = _requant(rng, cout, k_red)
            run = dict(op=job.op_kind, bits=bits, cin=job.cin,
                       stride=job.stride)
            cases.append(KernelCase(
                f"neureka {job.op_kind} b{bits} {job.h}x{job.w}x{job.cin}"
                f"->{cout} s{job.stride}",
                lambda x=x, p=packed, mu=mult, bi=bias, r=run:
                    ops.neureka_conv2d(x, p, mu, bi, mode=mode, **r),
                lambda x=x, p=packed, mu=mult, bi=bias, r=run:
                    ops.neureka_conv2d(x, p, mu, bi, mode="xla", **r),
                True))
    return cases


def phase_kernels(cases) -> None:
    failed = []
    for case in cases:
        got = np.asarray(jax.block_until_ready(jax.jit(case.kernel)()))
        want = np.asarray(jax.jit(case.oracle)())
        check(got.shape == want.shape,
              f"{case.name}: shape {got.shape} != oracle {want.shape}")
        if case.exact:
            err = int(np.max(np.abs(got.astype(np.int64)
                                    - want.astype(np.int64))))
            tol = 0
        else:
            got, want = got.astype(np.float32), want.astype(np.float32)
            err = float(np.max(np.abs(got - want)))
            tol = FLOAT_TOL * float(np.max(np.abs(want)))
        ok = bool(np.isfinite(got).all()) and err <= tol
        print(f"kernel {case.name}: max err {err!r} tol {tol!r} "
              f"{'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            failed.append(case.name)
    check(not failed, f"kernels off their oracles: {failed}")


# -- entry point -------------------------------------------------------------

def main(argv=None) -> dict:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the --mesh 4 sharded-paging phase")
    args = ap.parse_args(argv)

    device = device_guard(args.chips)
    run = Run()
    with Meter() as meter:
        cfg, packed = meter.phase("build", build_packed, run)
        # a per-device budget that keeps about half of the packed matmul
        # bytes resident (the mesh charges a sharded param 1/n per device)
        budget_mb = (sum(packed_sizes(packed).values()) / 2 / 2 ** 20
                     / args.chips)
        if args.chips == 4:
            meter.phase("link-placement", phase_link_placement, packed, 4)
            del packed
            meter.phase("mesh-serve", phase_mesh,
                        Run(requests=4, max_new=8), budget_mb, 4)
        else:
            meter.phase("resident-serve", phase_resident, run)
            meter.phase("forward-check", phase_forward, run, cfg, packed)
            del packed
            meter.phase("paged-serve", phase_paged, run, budget_mb)
            meter.phase("kernels", phase_kernels, kernel_cases("pallas"))
        print(f"total compile {meter.compile_s:.3f} s, "
              f"{meter.cache_hits} persistent-cache hits", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return device


if __name__ == "__main__":
    main()
