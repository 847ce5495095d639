"""Multi-device behaviour (sharding rules, compressed collectives, pipeline
parallelism, elastic checkpoint restore) — each case runs in a subprocess
with xla_force_host_platform_device_count so the main test process keeps
its single CPU device."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(code: str, n: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_param_shardings_single_device_equivalence():
    """In-process, one device: every arch's sharding specs divide the
    leaf shapes, and device_put under a 1x1 mesh is a value no-op — the
    rule set stays exercised even where the 8-device subprocess override
    is unavailable."""
    import jax
    import numpy as np
    from repro.configs import ARCHS
    from repro.launch.mesh import make_test_mesh
    from repro.launch.steps import param_specs, serve_param_specs
    from repro.models import transformer as tfm
    from repro.parallel import sharding as shd

    mesh = make_test_mesh((1, 1), ("data", "model"))
    for name, cfg in list(ARCHS.items())[:4]:
        for tree in (param_specs(cfg), serve_param_specs(cfg, 8)):
            shards = shd.param_shardings(tree, mesh)
            flat = jax.tree_util.tree_flatten_with_path(tree)[0]
            sflat = jax.tree_util.tree_leaves(shards)
            for (path, leaf), s in zip(flat, sflat):
                for dim, ax in zip(leaf.shape, s.spec):
                    if ax is None:
                        continue
                    size = mesh.shape[ax] if isinstance(ax, str) else 1
                    assert dim % size == 0, (name, path, leaf.shape, s.spec)
    cfg = list(ARCHS.values())[0].smoke()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    placed = jax.device_put(params, shd.param_shardings(params, mesh))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(placed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_param_sharding_rules_all_archs():
    """Every leaf's PartitionSpec divides its dimensions, for all 10 archs,
    dense and packed trees, on a (2, 4) data x model mesh."""
    run_devices("""
        import jax
        from repro.configs import ARCHS
        from repro.launch.mesh import make_test_mesh
        from repro.launch.steps import param_specs, serve_param_specs
        from repro.parallel import sharding as shd

        mesh = make_test_mesh((2, 4), ("data", "model"))
        for name, cfg in ARCHS.items():
            for tree in (param_specs(cfg), serve_param_specs(cfg, 8)):
                shards = shd.param_shardings(tree, mesh)
                flat = jax.tree_util.tree_flatten_with_path(tree)[0]
                sflat = jax.tree_util.tree_leaves(shards)
                for ((path, leaf), s) in zip(flat, sflat):
                    spec = s.spec
                    for dim, ax in zip(leaf.shape, spec):
                        if ax is None:
                            continue
                        size = mesh.shape[ax] if isinstance(ax, str) else 1
                        assert dim % size == 0, (name, path, leaf.shape, spec)
        print("OK")
    """)


@pytest.mark.slow
def test_distributed_train_step_matches_single_device():
    """A jitted train step on a 2x2 mesh equals the single-device result."""
    run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import ARCHS
        from repro.launch.mesh import make_test_mesh
        from repro.launch.steps import make_train_step, param_specs
        from repro.models import transformer as tfm
        from repro.optim import adamw
        from repro.parallel import sharding as shd

        cfg = ARCHS["qwen3-0.6b"].smoke().replace(
            d_model=64, n_heads=4, n_kv_heads=2, head_dim=16, vocab_size=256)
        opt = adamw()
        step = make_train_step(cfg, opt)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        rng = np.random.default_rng(0)
        batch = dict(tokens=jnp.asarray(rng.integers(0, 256, (4, 32))),
                     labels=jnp.asarray(rng.integers(0, 256, (4, 32))))

        ref_p, _, ref_m = jax.jit(step)(params, opt_state, batch)

        mesh = make_test_mesh((2, 2), ("data", "model"))
        pshard = shd.param_shardings(params, mesh)
        oshard = shd.opt_state_shardings(opt_state, mesh, params)
        with mesh:
            params_d = jax.device_put(params, pshard)
            opt_d = jax.device_put(opt_state, oshard)
            out_p, _, m = jax.jit(step)(params_d, opt_d, batch)
        assert abs(float(m["loss"]) - float(ref_m["loss"])) < 1e-4
        for a, b in zip(jax.tree_util.tree_leaves(ref_p),
                        jax.tree_util.tree_leaves(out_p)):
            np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                       np.asarray(b, dtype=np.float32),
                                       rtol=2e-3, atol=2e-3)
        print("OK")
    """)


@pytest.mark.slow
def test_compressed_allreduce():
    run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from repro.launch.mesh import make_test_mesh
        from repro.parallel.compress import (compressed_allreduce_mean,
                                             init_residual,
                                             with_error_feedback)

        mesh = make_test_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)

        f = shard_map(lambda x: compressed_allreduce_mean(x, "data"),
                      mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                      check_rep=False)
        out = f(g)                      # every shard holds the mean row
        expect = np.mean(np.asarray(g), axis=0)
        got = np.asarray(out)[0]
        # int8 compression: error bounded by ~scale = absmax/127
        bound = np.abs(np.asarray(g)).max() / 127 + 1e-6
        assert np.abs(got - expect).max() <= bound, np.abs(got - expect).max()

        # error feedback shrinks the accumulated bias over repeats
        def ef_step(x, r):
            return with_error_feedback(dict(g=x), dict(g=r), "data")
        f2 = shard_map(ef_step, mesh=mesh, in_specs=(P("data"), P("data")),
                       out_specs=(P("data"), P("data")), check_rep=False)
        r = jnp.zeros((8, 64))
        errs = []
        acc = np.zeros(64)
        for it in range(8):
            out, new_r = f2(g, r)
            acc += np.asarray(out["g"])[0]
            r = new_r["g"]
            errs.append(np.abs(acc / (it + 1) - expect).max())
        assert errs[-1] <= errs[0] + 1e-9
        print("OK")
    """)


@pytest.mark.slow
def test_pipeline_parallel_equivalence():
    run_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_test_mesh
        from repro.parallel.pipeline import bubble_fraction, pipelined_apply

        mesh = make_test_mesh((4,), ("stage",))
        rng = np.random.default_rng(0)
        ws = jnp.asarray(rng.normal(size=(4, 16, 16)) * 0.3, jnp.float32)

        def layer_fn(x, w):
            return jnp.tanh(x @ w)

        fn = pipelined_apply(layer_fn, mesh, "stage", n_microbatches=4)
        x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
        with mesh:
            out = fn(x, ws)
        ref = x
        for i in range(4):
            ref = layer_fn(ref, ws[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        assert bubble_fraction(4, 4) == (4 - 1) / (4 - 1 + 4)
        print("OK")
    """)


def test_opt_state_shardings_keyed_by_path_not_shape():
    """Two same-shape params with DIFFERENT partition specs must keep
    their own specs through the optimizer-state mirror — the shape-keyed
    lookup this replaces silently collided (last-one-wins)."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_test_mesh
    from repro.parallel import sharding as shd

    mesh = make_test_mesh((1, 1), ("data", "model"))
    # conv_w's rule is P(model, None); a generic 2-D (out, in) matmul
    # weight gets P(model, data) — same (8, 8) shape, different specs
    params = dict(conv_w=jnp.zeros((8, 8)), wq=jnp.zeros((8, 8)))
    assert (shd._param_pspec(("conv_w",), (8, 8), mesh)
            != shd._param_pspec(("wq",), (8, 8), mesh))
    opt_state = dict(mu=params, nu=params)
    out = shd.opt_state_shardings(opt_state, mesh, params)
    for moment in ("mu", "nu"):
        assert out[moment]["conv_w"].spec == P("model", None)
        assert out[moment]["wq"].spec == P("model", "data")


def test_make_test_mesh_clamps_to_available_devices():
    """A shape wanting more devices than the host exposes is an error —
    never a smaller mesh that would pass for the one asked for."""
    import jax
    from repro.launch.mesh import make_test_mesh

    want = (jax.device_count() + 1, 2)
    with pytest.raises(ValueError, match="needs"):
        make_test_mesh(want, ("data", "model"))
    mesh = make_test_mesh((1, jax.device_count()), ("data", "model"))
    assert tuple(mesh.axis_names) == ("data", "model")
    assert mesh.devices.size == jax.device_count()


def test_plan_for_budget_charges_sharded_params_per_device():
    """shard_factors: a param sharded n ways pins only 1/n of its bytes
    per device, so a tight per-device budget admits it resident where
    the unsharded charge would have paged it."""
    from repro.core.placement import Placement, plan_for_budget

    sizes = {"a": 1000, "b": 1000}
    hot = Placement("l1mram", 8, "resident")
    cold = Placement("l3flash", 8, "paged")
    flat = plan_for_budget(sizes, 500, hot=hot, cold=cold)
    assert flat.placement_for("a").residency == "paged"
    assert flat.placement_for("b").residency == "paged"
    plan = plan_for_budget(sizes, 500, hot=hot, cold=cold,
                           shard_factors={"a": 4})
    assert plan.placement_for("a").residency == "resident"  # 250 B/device
    assert plan.placement_for("b").residency == "paged"     # 1000 > 250 left
    # per-device budget respected: resident charge is the sharded one
    assert -(-sizes["a"] // 4) <= 500


def test_packed_sizes_shard_factors_divide():
    import numpy as np
    from repro.core.placement import packed_sizes

    tree = {"wq": {"packed": np.zeros((8, 16), np.uint8),
                   "scale": np.zeros((8, 1), np.float32)}}
    whole = packed_sizes(tree)
    per_dev = packed_sizes(tree, shard_factors={"wq": 4})
    assert whole["wq"] == 128
    assert per_dev["wq"] == -(-whole["wq"] // 4)


_SHARDED_SERVE = """
    import json, os, sys, tempfile
    from repro.launch import serve

    path = os.path.join(tempfile.mkdtemp(), "BENCH_mesh_test.json")
    argv = ["--smoke", "--budget-mb", "0.05", "--requests", "3",
            "--max-new", "4", "--mesh", "4", "--metrics-json", path]
    {extra}
    serve.main(argv)
    doc = json.load(open(path))
    mesh = doc["mesh"]
    assert mesh["n_devices"] == 4, mesh
    assert mesh["sharded_params"] > 0, mesh
    assert mesh["bit_exact"] is True, mesh
    assert mesh["predicted_ok"] is True, mesh
    assert mesh["ledger_ok"] is True, mesh
    led = mesh["ledger"]
    assert len(led["per_device"]) == 4
    for key in ("swap_count", "miss_count", "bytes_streamed_wire",
                "bytes_streamed_raw"):
        assert led[key] == sum(d[key] for d in led["per_device"]), key
    # the global ledger equals the single-device one; every link moves
    # strictly less than the single link did
    single = mesh["single_device"]
    assert led["bytes_streamed_wire"] == single["bytes_streamed_wire"]
    assert mesh["per_link_max_wire"] < single["bytes_streamed_wire"]
    assert doc["paging"]["devices"] == led["per_device"]
    print("OK")
"""


@pytest.mark.slow
def test_sharded_serving_bit_exact_fp_pages():
    """Mesh-sharded paged serving (fp pages) on a 1x4 mesh: serve.main's
    verify legs gate tokens bit-exact vs the single-device paged run
    (async AND sync — the sync leg is meshed too) and the per-device
    ledger summing to the global kv_pass_counters prediction."""
    run_devices(_SHARDED_SERVE.format(extra=""), n=4)


@pytest.mark.slow
def test_sharded_serving_bit_exact_int8_pages():
    """Same gates with int8-encoded page wire (--page-bits 8, the
    run-quantized identity): per-row scales slice along the shard axis
    with their rows, so shard-then-encode == encode-then-shard."""
    run_devices(
        _SHARDED_SERVE.format(extra='argv += ["--page-bits", "8"]'), n=4)


@pytest.mark.slow
def test_sharded_store_join_and_no_orphaned_pass():
    """ShardedPagedStore mechanics, below the engine: the joined fence
    reconstructs every sharded param's device bytes exactly, and an
    early close releases EVERY per-device pool's pass guard (no orphaned
    pass blocks the next one)."""
    run_devices("""
        import numpy as np
        import jax
        from repro.configs import ARCHS
        from repro.core.paging import ShardedPagedStore, packed_tree_store
        from repro.launch.mesh import make_test_mesh
        from repro.models import transformer as tfm
        from repro.parallel.sharding import freeze_for_serving

        cfg = ARCHS["qwen3-0.6b"].smoke().replace(
            d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
            vocab_size=256)
        packed = freeze_for_serving(
            tfm.init_params(cfg, jax.random.PRNGKey(0)), bits=8)
        store = packed_tree_store(packed, None)   # plan-less: all paged
        mesh = make_test_mesh((1, 4), ("data", "model"))
        page_bytes = max(p.nbytes_packed for p in store.params.values())
        sps = ShardedPagedStore(store, page_bytes, mesh, plan=None,
                                budget_bytes=1 << 22)
        assert sps.shard_axes, "smoke net must shard something"

        # every link fetches onto its OWN device, not all onto device 0
        assert len(set(sps.devices)) == 4
        for sub in sps.stores:
            with sub.begin_pass() as ps:
                fetched = ps.fence()
            for p in fetched.values():
                assert p.packed.devices() == {sub.device}, sub.name
                assert p.scale.devices() == {sub.device}, sub.name

        # a fenced pass joins the per-device fetches byte-exactly
        with sps.begin_pass() as ps1:
            dev = ps1.fence()
        for name, (ax, n) in sps.shard_axes.items():
            np.testing.assert_array_equal(
                np.asarray(dev[name].packed),
                np.asarray(store.params[name].packed))
            np.testing.assert_array_equal(
                np.asarray(dev[name].scale),
                np.asarray(store.params[name].scale))
            assert dev[name].orig_shape == store.params[name].orig_shape

        # runtime counters match the ledger's static prediction (every
        # begun pass fenced so far — the determinism precondition)
        pred = sps.predict()
        assert sps.swap_count == pred["swaps"], (sps.swap_count, pred)
        assert sps.bytes_streamed_wire == pred["bytes_wire"]

        # early close: the joined stream was never fenced, yet every
        # per-device pool guard is released — no orphaned pass
        ps = sps.begin_pass()
        ps.close()
        for pool in sps.ledger.pools:
            assert not pool._active_fetch, pool._active_fetch
        try:
            ps.fence()
            raise AssertionError("fence after close must raise")
        except RuntimeError:
            pass

        # and the store still serves: the next pass begins and fences
        with sps.begin_pass() as ps3:
            dev3 = ps3.fence()
        assert set(dev3) == set(dev)
        sps.close()
        print("OK")
    """, n=4)


@pytest.mark.slow
def test_elastic_checkpoint_restore_across_meshes(tmp_path):
    """Save sharded on a (4,2) mesh, restore onto (2,4) — elastic scaling."""
    run_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint import CheckpointManager
        from repro.launch.mesh import make_test_mesh
        from repro.parallel import sharding as shd
        from repro.models import transformer as tfm
        from repro.configs import ARCHS

        cfg = ARCHS["olmo-1b"].smoke()
        params = tfm.init_params(cfg, jax.random.PRNGKey(1))

        mesh_a = make_test_mesh((4, 2), ("data", "model"))
        shard_a = shd.param_shardings(params, mesh_a)
        params_a = jax.device_put(params, shard_a)

        mgr = CheckpointManager(r"{tmp_path}", async_save=False)
        mgr.save(3, dict(params=params_a))

        mesh_b = make_test_mesh((2, 4), ("data", "model"))
        shard_b = shd.param_shardings(params, mesh_b)
        step, state = mgr.restore(dict(params=params),
                                  shardings=dict(params=shard_b))
        assert step == 3
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(state["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # restored arrays live on the NEW mesh
        leaf = jax.tree_util.tree_leaves(state["params"])[0]
        assert leaf.sharding.mesh.shape == {{"data": 2, "model": 4}}
        print("OK")
    """)
