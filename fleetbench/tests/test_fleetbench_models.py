"""A configuration's model file: how the harness finds it, what it must
provide, and the reference's use of it (trainable leaves packed,
aggregated and cached, frozen ones held fixed)."""
import json
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetbench import harness, reference
from fleetbench.tests import golden
from fleetbench.tests.tiny import BENCHMARKED, ROOT, file_cell, tiny

GOLDEN = Path(__file__).parent / "data" / "reference_golden.json"
MLP_FILE = ROOT / "fleetbench" / "models" / "mlp.py"


def _same_digest(got: dict, want: dict, what: str):
    assert got["dtype"] == want["dtype"] and got["shape"] == want["shape"], \
        what
    assert np.array_equal(np.asarray(got["sample"]),
                          np.asarray(want["sample"])), what
    assert got["sha256"] == want["sha256"], what


@pytest.mark.parametrize("files", golden.CELLS, ids=".".join)
def test_reference_reproduces_the_recorded_outputs_bit_for_bit(files):
    """The data and the reference's theta0, globals, losses and cache
    rows equal, bit for bit, what the reference gave before its model
    code moved into models/mlp.py (recorded on this cell at seed 5)."""
    want = json.loads(GOLDEN.read_text())[".".join(files)]
    spec = harness.spec_of(tiny(file_cell(*files)))
    data = spec["model_code"].make_data(golden.SEED, spec["data"])
    got = golden.snapshot(reference.simulate(
        spec, data, golden.SEED, golden.ROUNDS,
        numeric_rounds=golden.ROUNDS), data)
    for k in want["data"]:
        _same_digest(got["data"][k], want["data"][k], f"data.{k}")
    _same_digest(got["theta0"], want["theta0"], "theta0")
    assert len(got["globals"]) == len(want["globals"]) == golden.ROUNDS
    for r, (g, w) in enumerate(zip(got["globals"], want["globals"])):
        _same_digest(g, w, f"globals[{r}]")
    assert np.array_equal(np.asarray(got["losses"], np.float64),
                          np.asarray(want["losses"], np.float64))
    assert list(got["cache_after"]) == list(want["cache_after"])
    assert want["cache_after"], "the cell caches no row to compare"
    for c in want["cache_after"]:
        _same_digest(got["cache_after"][c], want["cache_after"][c],
                     f"cache_after[{c}]")
    for k in ("leaf_sizes", "selected", "received", "wall_clock"):
        assert got[k] == want[k], k


def test_mlp_leaves_flatten_as_the_program_does():
    from repro.fl import classifier as CLF
    model = harness.load_json(ROOT / "fleetbench" / "configs"
                              / "xdevice-flude.json")["model"]
    mlp = harness.load_model(model["kind"])
    tmpl = CLF.init_classifier(
        jax.random.key(1), dim=model["dim"], num_classes=model["num_classes"],
        hidden=model["hidden"], depth=model["depth"])
    leaves = jax.tree_util.tree_flatten_with_path(tmpl)[0]
    program = [("/".join(str(k.key) for k in path), tuple(leaf.shape))
               for path, leaf in leaves]
    assert list(mlp.leaf_shapes(model).items()) == program
    assert mlp.packed_dim(model) == sum(int(np.prod(s)) for _, s in program)
    trainable, frozen = mlp.init_params(3, model)
    assert frozen == {} and set(trainable) == set(mlp.leaf_shapes(model))


# ---------------------------------------------------------------------------
# Resolve names what is missing
# ---------------------------------------------------------------------------

def _tree(tmp_path, edit_model) -> Path:
    """A copy of the benchmark's files under ``tmp_path`` whose
    benchmarked configuration's model block ``edit_model`` changed."""
    base = tmp_path / "fleetbench"
    for sub in ("configs", "traffic", "limits", "metrics", "models"):
        shutil.copytree(ROOT / "fleetbench" / sub, base / sub)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    path = base / "configs" / f"{BENCHMARKED.split('.')[0]}.json"
    cfg = json.loads(path.read_text())
    edit_model(cfg["model"])
    path.write_text(json.dumps(cfg))
    return base


def test_resolve_refuses_a_config_without_a_model_kind(tmp_path):
    base = _tree(tmp_path, lambda m: m.pop("kind"))
    with pytest.raises(KeyError, match="names no model kind"):
        harness.resolve(tmp_path, BENCHMARKED, base=base)


def test_resolve_refuses_a_kind_without_a_model_file(tmp_path):
    base = _tree(tmp_path, lambda m: m.update(kind="nosuch"))
    with pytest.raises(FileNotFoundError, match=r"models/nosuch\.py"):
        harness.resolve(tmp_path, BENCHMARKED, base=base)


@pytest.mark.parametrize("fn", harness.MODEL_API)
def test_resolve_refuses_a_model_file_without_a_function(fn, tmp_path):
    base = _tree(tmp_path, lambda m: m.update(kind="broken"))
    (base / "models" / "broken.py").write_text(
        MLP_FILE.read_text() + f"\ndel {fn}\n")
    with pytest.raises(AttributeError,
                       match=rf"models/broken\.py has no {fn}\(\)"):
        harness.resolve(tmp_path, BENCHMARKED, base=base)


# ---------------------------------------------------------------------------
# Frozen leaves
# ---------------------------------------------------------------------------

FROZEN_MODEL = '''
import jax
import jax.numpy as jnp

from fleetbench import harness

_mlp = harness.load_model("mlp")
HIGHEST = jax.lax.Precision.HIGHEST


def make_data(seed, spec):
    """The MLP's data as token ids: each sample's first ``seq`` values,
    bucketed into a vocabulary of ``vocab``."""
    d = _mlp.make_data(seed, spec)
    tok = lambda a: (jnp.floor(jnp.abs(a[..., :spec["seq"]]) * 8.0)
                     .astype(jnp.int32) % spec["vocab"])
    return d._replace(x=tok(d.x), test_x=tok(d.test_x))


def leaf_shapes(model):
    return {"out/b": (model["classes"],),
            "out/w": (model["width"], model["classes"])}


def init_params(seed, model):
    k_e, k_w, k_r = jax.random.split(jax.random.key(seed + 1), 3)
    v, p, c = model["vocab"], model["width"], model["classes"]
    trainable = {"out/b": jnp.zeros((c,), jnp.float32),
                 "out/w": jax.random.normal(k_w, (p, c)) / p ** 0.5}
    frozen = {"embed": jax.random.normal(k_e, (v, p)),
              "remap": jax.random.permutation(k_r, v).astype(jnp.int32)}
    return trainable, frozen


def loss_fn(trainable, frozen, x, y, model):
    h = jnp.tanh(frozen["embed"][frozen["remap"][x]].mean(axis=-2))
    lg = jnp.dot(h, trainable["out/w"], precision=HIGHEST) \\
        + trainable["out/b"]
    lp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.take_along_axis(lp, y[:, None], axis=-1).mean()


def packed_dim(model):
    return (model["width"] + 1) * model["classes"]


def train_flops(model, samples):
    return 6.0 * model["width"] * model["classes"] * samples


def eval_flops(model, samples):
    return 2.0 * model["width"] * model["classes"] * samples
'''


def test_frozen_leaves_are_never_packed_aggregated_or_cached(
        tmp_path, monkeypatch):
    """A model with frozen leaves (an embedding over token data and an
    integer index table): only its trainable leaves are packed,
    aggregated and cached, the table keeps its integer dtype, and round
    0 is SGD and the mean over the trainable leaves with the frozen ones
    closed over."""
    (tmp_path / "models").mkdir()
    (tmp_path / "models" / "frozen_probe.py").write_text(FROZEN_MODEL)
    code = harness.load_model("frozen_probe", base=tmp_path)
    spec = harness.spec_of(tiny(file_cell("xdevice-flude", "diurnal")))
    spec["data"].update(seq=8, vocab=32)
    model = {"kind": "frozen_probe", "vocab": 32, "width": 16,
             "classes": spec["data"]["num_classes"]}
    spec.update(model=model, model_code=code)
    packed = []
    orig_pack = reference.pack

    def pack(params, names):
        packed.append(set(params) | set(names))
        return orig_pack(params, names)

    monkeypatch.setattr(reference, "pack", pack)
    seed, sim = 5, spec["sim"]
    data = code.make_data(seed, spec["data"])
    assert data.x.dtype == jnp.int32 and data.x.shape[-1] == 8
    ref = reference.simulate(spec, data, seed, 3, numeric_rounds=3)
    # The control's bfloat16 reference keeps the index table integer as
    # well: a floating index would raise.
    reference.simulate(spec, data, seed, 1, numeric_rounds=1,
                       dtype=jnp.bfloat16)

    d = code.packed_dim(model)
    assert ref["leaf_names"] == ["out/b", "out/w"]
    assert ref["leaf_sizes"] == [10, 160] and sum(ref["leaf_sizes"]) == d
    assert packed and all(p == {"out/b", "out/w"} for p in packed)
    assert ref["theta0"].shape == (d,)
    assert all(g.shape == (d,) for g in ref["globals"])
    assert ref["cache_after"], "no cache row to look at"
    assert all(r.shape == (d,) for r in ref["cache_after"].values())

    # Round 0 by hand: every received client ran all its local steps
    # from theta0 (nothing is cached yet), and the mean weighs them
    # alike (no staleness, no adversary).
    trainable, frozen = code.init_params(seed, model)
    n, b = data.x.shape[1], int(sim["batch_size"])
    received = np.flatnonzero(np.asarray(ref["first"][0]["received"]))
    assert received.size > 0

    def round0(trunk):
        grad = jax.jit(jax.grad(
            lambda p, x, y: code.loss_fn(p, trunk, x, y, model)))
        finals = []
        for c in received:
            p = trainable
            for j in range(int(sim["local_steps"])):
                sl = (j * b + np.arange(b)) % n
                g = grad(p, data.x[c, sl], data.y[c, sl])
                p = jax.tree.map(lambda a, ga: a - float(sim["lr"]) * ga,
                                 p, g)
            finals.append(np.concatenate([np.asarray(p[k]).reshape(-1)
                                          for k in ref["leaf_names"]]))
        return np.mean(finals, axis=0)

    np.testing.assert_allclose(ref["globals"][0], round0(frozen),
                               rtol=1e-5, atol=1e-6)
    # The trunk is used: with it zeroed the same steps land elsewhere.
    zeroed = jax.tree.map(jnp.zeros_like, frozen)
    assert not np.allclose(ref["globals"][0], round0(zeroed),
                           rtol=1e-5, atol=1e-6)
