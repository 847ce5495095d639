"""Everything that depends on a model's family sits behind one module per
family, found by name: the dense family's weights are the bits they were
before it moved there, a family and a reference added as files in a
temporary directory run a whole cell (one whose engine prefills as a
mixture of experts does, warmed up by its own family), and the generic
harness names no family."""

import ast
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import bench_tiny
from bench import run, xplane
from bench.registry import BENCH, FAMILY_API, Registry

dense = Registry().family("dense")

SEED = 2**31 + 77

# sha256 over the leaves of make_weights(d, seed), as the harness made them
# before the family modules (one line "path=leaf hash;" per leaf, sorted)
WEIGHTS = {
    ("tiny", 7): "bab7a9092b82d633",
    ("tiny", 2**40 + 1): "d0b3237f8a495b2d",
    ("tiny-paged", 7): "18e10b2663cd78a6",
    ("tiny-paged", 2**40 + 1): "b52dcbdefd9fb4fc",
}


def _leaf_hashes(tree):
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        out[jax.tree_util.keystr(path)] = hashlib.sha256(
            f"{a.dtype}{a.shape}".encode() + a.tobytes()).hexdigest()[:16]
    return out


@pytest.mark.parametrize("config,seed", sorted(WEIGHTS))
def test_bench_dense_weights_are_the_recorded_bits(tmp_path, config, seed):
    reg = bench_tiny.registry(tmp_path)
    d = dense.dims(config, reg.config(config))
    leaves = _leaf_hashes(dense.make_weights(d, seed))
    digest = hashlib.sha256("".join(
        f"{p}={h};" for p, h in sorted(leaves.items())).encode())
    assert digest.hexdigest()[:16] == WEIGHTS[config, seed], leaves


# a second family and reference, written as files beside their config: each
# delegates to the dense one and records its calls in calls.txt next to it
SEAM_FAMILY = '''
from pathlib import Path

from bench.registry import Registry

dense = Registry().family("dense")

CALLS = Path(__file__).resolve().parents[1] / "calls.txt"


def _recorded(name):
    def call(*a, **kw):
        with open(CALLS, "a") as f:
            f.write(name + "\\n")
        return getattr(dense, name)(*a, **kw)
    return call


for _name in %r:
    globals()[_name] = _recorded(_name)
''' % (FAMILY_API,)

SEAM_REFERENCE = '''
from pathlib import Path

from bench.registry import Registry

dense_decoder = Registry().reference("dense_decoder")

CALLS = Path(__file__).resolve().parents[1] / "calls.txt"


def score(*a, **kw):
    with open(CALLS, "a") as f:
        f.write("reference.score\\n")
    return dense_decoder.score(*a, **kw)
'''


def _tree_digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_bench_family_added_as_files_runs_a_cell(tmp_path, monkeypatch):
    """A cell whose configuration names the family ``seam`` runs through
    ``run.measure`` with a traced window: set-up, warm-up, the window, the
    count readers and the comparison each reach the family, found by name
    in the temporary directory, and the run reads correct."""
    before = _tree_digest(BENCH)
    reg = bench_tiny.registry(tmp_path)
    for sub, text in (("families/seam.py", SEAM_FAMILY),
                      ("references/seam_ref.py", SEAM_REFERENCE)):
        (tmp_path / sub).parent.mkdir(exist_ok=True)
        (tmp_path / sub).write_text(text)
    conf = dict(bench_tiny.TINY, reference="seam_ref",
                model=dict(bench_tiny.TINY["model"], family="seam"))
    (tmp_path / "configs" / "seam-tiny.json").write_text(json.dumps(conf))
    reg.doc["workloads"].append(
        {"name": "seam-tiny.batch", "config": "seam-tiny",
         "traffic": "batch-tiny", "chips": 1, "why": "test"})
    # the device-trace readers read a decode program from the trace; the
    # CPU's trace has no device plane, so its reduction is stood in for
    monkeypatch.setattr(run, "TRACE_DIR", tmp_path / "trace")
    monkeypatch.setattr(xplane, "reduce_trace", lambda p: dict(
        busy_s=0.5, window_s=1.0, n_devices=1,
        programs={"jit__decode_impl": [0.01]}, device_ops=[], idle_gaps=[]))

    res = run.measure(reg, "seam-tiny.batch", SEED, 1.5, True,
                      run.devices_for(1, allow_cpu=True))
    assert res["correct"] is True
    assert res["window_compiles"] == 0
    for name in ("decode_roofline", "decode.mfu"):
        assert res["metrics"][name]["value"] > 0, name
    calls = set((tmp_path / "calls.txt").read_text().split())
    assert calls == set(FAMILY_API) | {"reference.score"}
    assert _tree_digest(BENCH) == before


# a family whose engine path is not dense's: the dense decoder's MLP served
# as the one expert of a mixture (top-1 of one, so its gate is exactly 1
# and the dense reference holds).  The engine prefills a mixture of experts
# one row at a time at the prompt's exact length, so the family's own
# warm-up runs those programs; the dense warm-up (all slots' rows, pow2
# buckets) leaves them to compile inside the window
MOE_FAMILY = '''
import dataclasses

import jax.numpy as jnp

from bench import traffic
from bench.registry import Registry

dense = Registry().family("dense")
dims = dense.dims
decode_call = dense.decode_call
prefill_flops = dense.prefill_flops


def model_config(d):
    return dataclasses.replace(dense.model_config(d), family="moe",
                               n_experts=1, n_experts_active=1,
                               moe_d_ff=d.d_ff)


def make_weights(d, seed):
    w = dense.make_weights(d, seed)
    layers = dict(w["layers"])
    moe = {n: {"packed": m["packed"][:, None], "scale": m["scale"][:, None]}
           for n, m in layers.pop("mlp").items()}
    moe["router"] = jnp.zeros((d.n_layers, 1, d.d_model), jnp.float32)
    return dict(w, layers=dict(layers, moe=moe))


def warm_prefill(eng, mix, d):
    if WARM == "dense":
        return dense.warm_prefill(eng, mix, d)
    params = eng.fence_tick_params()
    pmin, pmax = traffic.length_range(mix, "prompt")
    for n in range(pmin, pmax + 1):
        span = min(d.max_len, dense.next_pow2(n))
        fn = eng._prefill_for_bucket(n, True, span)
        logits, cache = fn(params, jnp.zeros((1, n), jnp.int32), eng.cache,
                           jnp.zeros((1,), jnp.int32),
                           jnp.zeros((1,), jnp.int32))
        del cache
        int(jnp.argmax(logits[0, n - 1]))
        del logits
'''

MOE_REFERENCE = '''
from bench.registry import Registry

dense_decoder = Registry().reference("dense_decoder")


def score(d, weights, *a, **kw):
    layers = dict(weights["layers"])
    moe = dict(layers.pop("moe"))
    del moe["router"]
    mlp = {n: {"packed": m["packed"][:, 0], "scale": m["scale"][:, 0]}
           for n, m in moe.items()}
    weights = dict(weights, layers=dict(layers, mlp=mlp))
    return dense_decoder.score(d, weights, *a, **kw)
'''

MOE_MIX = dict(bench_tiny.BATCH, streams=[dict(
    bench_tiny.BATCH["streams"][0],
    prompt={"dist": "uniform", "lo": 8, "hi": 12},
    new_tokens={"dist": "uniform", "lo": 4, "hi": 8})])


@pytest.mark.parametrize("warm", ["own", "dense"])
def test_bench_family_warms_up_its_own_prefill(tmp_path, warm):
    """A mixture-of-experts family, added as files, runs a cell that reads
    correct.  With its own warm-up nothing compiles inside the window;
    with the dense warm-up in its place the window compiles, so the zero
    is the family's doing and not the harness's."""
    reg = bench_tiny.registry(tmp_path)
    for sub in ("families", "references"):
        (tmp_path / sub).mkdir()
    (tmp_path / "families" / "moe1.py").write_text(
        f"WARM = {warm!r}\n" + MOE_FAMILY)
    (tmp_path / "references" / "moe1_ref.py").write_text(MOE_REFERENCE)
    conf = dict(bench_tiny.TINY, reference="moe1_ref",
                model=dict(bench_tiny.TINY["model"], family="moe1"))
    (tmp_path / "configs" / "moe1-tiny.json").write_text(json.dumps(conf))
    (tmp_path / "traffic" / "moe-tiny.json").write_text(json.dumps(MOE_MIX))
    reg.doc["workloads"].append(
        {"name": "moe1-tiny.batch", "config": "moe1-tiny",
         "traffic": "moe-tiny", "chips": 1, "why": "test"})

    res = run.measure(reg, "moe1-tiny.batch", SEED, 1.5, False,
                      run.devices_for(1, allow_cpu=True))
    assert res["correct"] is True
    if warm == "own":
        assert res["window_compiles"] == 0
    else:
        assert res["window_compiles"] > 0


def test_bench_family_lookup_errors(tmp_path):
    reg = bench_tiny.registry(tmp_path)
    with pytest.raises(KeyError, match=r"families/nosuch\.py"):
        reg.family("nosuch")
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "partial.py").write_text(
        "from bench.registry import Registry\n"
        "_dense = Registry().family('dense')\n"
        "dims, model_config, make_weights = (\n"
        "    _dense.dims, _dense.model_config, _dense.make_weights)\n")
    with pytest.raises(AttributeError, match="'partial'.*decode_call, "
                       "prefill_flops, warm_prefill"):
        reg.family("partial")


def test_bench_generic_code_names_no_family():
    """The modules at ``bench/``'s top level and the metric readers import
    no family module and hold no family's name: what depends on a family
    is reached through the registry or the window's ``family``."""
    families = {p.stem for p in (BENCH / "families").glob("*.py")
                if not p.name.startswith("_")}
    assert "dense" in families
    generic = sorted(BENCH.glob("*.py")) + sorted(
        (BENCH / "metrics").glob("*.py"))
    for path in generic:
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                names = [node.module or ""] + [
                    f"{node.module}.{a.name}" for a in node.names]
            elif isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            else:
                names = []
            assert not any(n.startswith("bench.families") for n in names), \
                f"{path.name} imports {names}"
            if isinstance(node, ast.Constant):
                assert node.value not in families, \
                    f"{path.name} names the family {node.value!r}"
