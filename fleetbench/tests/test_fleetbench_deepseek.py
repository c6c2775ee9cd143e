"""DeepSeek-V2-Lite on the fleet path, at tiny widths on the CPU, against
the plain reference of ``models/deepseek_v2_lite.py``: MLA with YaRN and
no q_lora, the held-expert MoE layer and its share of the uncut layer,
the whole loss and its adapter gradients, the program's leaves and
initial weights, the frozen trunk kept out of packing, aggregation and
caches, and a tiny copy of the cell through ``run_cell``."""
import copy
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fleetbench import harness
from fleetbench.tests.tiny import ROOT, run, tiny
from repro.configs import deepseek_v2_lite as DS
from repro.configs.base import LoRAConfig, MLAConfig
from repro.core import aggregation as AG
from repro.fl import models as FLM
from repro.models import attention as A
from repro.models import lora_lm as LM
from repro.models import moe as M

REF = harness.load_model("deepseek_v2_lite")
CONFIG = json.loads((ROOT / "fleetbench" / "configs"
                     / "deepseek-v2-lite.json").read_text())
WORKLOAD = "deepseek-v2-lite.diurnal"
TINY = "deepseek-v2-lite-tiny"
TINY_LORA = LoRAConfig(rank=4, alpha=8.0)


def tiny_cfg(dtype="float32", held=2, experts=16):
    """DeepSeek-V2-Lite's structure at tiny widths: a dense layer and two
    MoE layers, ``held`` of ``experts`` routed experts, 3 a token."""
    return dataclasses.replace(
        DS.FLEET, d_model=64, num_heads=4, num_kv_heads=4, d_ff=96,
        vocab_size=128, num_layers=3, param_dtype=dtype,
        mla=MLAConfig(q_lora_rank=None, kv_lora_rank=32,
                      qk_nope_head_dim=16, qk_rope_head_dim=8,
                      v_head_dim=16),
        moe=dataclasses.replace(DS.FLEET.moe, num_experts=experts, top_k=3,
                                expert_d_ff=32, shared_d_ff=32,
                                experts_held=held))


def block(cfg, lora=TINY_LORA, first=0, seq_len=16):
    """The configuration's ``model`` block for a program ModelConfig."""
    m, e = cfg.mla, cfg.moe
    held = e.experts_held or e.num_experts
    b = copy.deepcopy(CONFIG["model"])
    b.update(hidden_size=cfg.d_model, num_hidden_layers=cfg.num_layers,
             first_k_dense_replace=e.first_k_dense_replace,
             intermediate_size=cfg.d_ff, moe_intermediate_size=e.expert_d_ff,
             num_attention_heads=cfg.num_heads, kv_lora_rank=m.kv_lora_rank,
             qk_nope_head_dim=m.qk_nope_head_dim,
             qk_rope_head_dim=m.qk_rope_head_dim, v_head_dim=m.v_head_dim,
             n_routed_experts=held, expert_parallel=e.num_experts // held,
             first_expert=first, num_experts_per_tok=e.top_k,
             n_shared_experts=e.num_shared_experts,
             vocab_size=cfg.vocab_size, seq_len=seq_len,
             rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
             lora_rank=lora.rank, lora_alpha=lora.alpha)
    return b


def as_program(tree_like, flat):
    """A reference flat ``{name: leaf}`` dict in the program's tree."""
    names = [n for n, _ in LM.named_leaves(tree_like)]
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(tree_like), [flat[n] for n in names])


def perturbed(adapters, seed=0, scale=0.1):
    """Adapters with ``b`` moved off zero, so every path is exercised."""
    key = jax.random.key(seed)
    return {k: v + scale * jax.random.normal(jax.random.fold_in(key, i),
                                             v.shape)
            for i, (k, v) in enumerate(sorted(adapters.items()))}


def sub(tree, prefix):
    return {k[len(prefix):]: v for k, v in tree.items()
            if k.startswith(prefix)}


@pytest.fixture(scope="module")
def weights():
    cfg = tiny_cfg()
    model = block(cfg)
    ad, tr = REF.init_params(3, model)
    return cfg, model, perturbed(ad), tr


def _hidden(seed, shape):
    return jax.random.normal(jax.random.key(seed), shape, jnp.float32)


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def test_mla_with_yarn_and_no_q_lora_matches_reference(weights):
    cfg, model, ad, tr = weights
    assert cfg.mla.q_lora_rank is None and cfg.rope_scaling is not None
    x = _hidden(1, (2, 16, cfg.d_model))
    p_ref = sub(tr, "layers/1/")
    want = REF.attention(x, p_ref, sub(ad, "layers/1/"), model,
                         TINY_LORA.scale)
    prog_tr = as_program(LM.init_trunk(cfg, 3), tr)
    prog_ad = as_program(LM.init_adapters(cfg, TINY_LORA, 3), ad)
    adx = jax.tree.map(lambda a: a[None], prog_ad["layers"][1])
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    got = A.mla_forward(prog_tr["layers"][1]["attn"], x, pos, cfg,
                        impl="dense",
                        adapter=LM._adapter(adx, 1, TINY_LORA.scale))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    assert "wq" in prog_tr["layers"][1]["attn"]
    assert "w_dq" not in prog_tr["layers"][1]["attn"]
    # the published softmax scale: 192^-1/2 * (0.1 * 0.707 * ln 40 + 1)^2
    assert A.mla_softmax_scale(DS.CONFIG) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2)


def test_mla_norm_eps_comes_from_the_config(weights):
    cfg, model, ad, tr = weights
    prog_tr = as_program(LM.init_trunk(cfg, 3), tr)
    x = 1e-3 * _hidden(2, (1, 8, cfg.d_model))
    pos = jnp.arange(8)[None]
    p = prog_tr["layers"][1]["attn"]
    a = A.mla_forward(p, x, pos, cfg, impl="dense")
    b = A.mla_forward(p, x, pos, dataclasses.replace(cfg, norm_eps=1e-2),
                      impl="dense")
    assert not np.allclose(a, b)


@pytest.mark.parametrize("held,first", [(2, 0), (16, 0), (2, 6)])
def test_held_moe_matches_reference(held, first):
    """No renormalisation of the top-k gates, no drops, shared experts
    once; the share may be any run of experts."""
    cfg = tiny_cfg(held=held)
    model = block(cfg, first=first)
    _, tr = REF.init_params(5, model)
    p_ref = sub(tr, "layers/1/")
    x = _hidden(3, (1, 64, cfg.d_model))
    want = REF.moe(x, p_ref, model)[0]
    prog = as_program(LM.init_trunk(cfg, 5), tr)["layers"][1]["moe"]
    got, load = M.held_moe_forward(prog, x[0], cfg, first_expert=first)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    gates, experts = M.route(prog["router"], x[0], cfg)
    # every (token, slot) pair on a held expert ran: none dropped
    mine = (experts >= first) & (experts < first + held)
    assert int(load.sum()) == int(mine.sum())
    assert float(gates.sum(-1).max()) < 1.0      # not renormalised


def test_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts of the 8 shares, with the shared experts counted
    once, add up to the uncut reference layer (``model-configs`` §4)."""
    full = tiny_cfg(held=16)
    model = block(full)
    _, tr = REF.init_params(7, model)
    p_full = sub(tr, "layers/2/")
    x = _hidden(4, (1, 48, full.d_model))
    want = REF.moe(x, p_full, model)[0]
    share_cfg = tiny_cfg(held=2)
    prog_full = as_program(LM.init_trunk(full, 7), tr)["layers"][2]["moe"]
    total = jnp.zeros_like(want)
    for s in range(8):
        p = dict(prog_full,
                 **{k: prog_full[k][2 * s:2 * s + 2]
                    for k in ("wi", "wg", "wo")})
        part, _ = M.held_moe_forward(p, x[0], share_cfg, first_expert=2 * s,
                                     with_shared=(s == 0))
        total = total + part
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# The whole model
# ---------------------------------------------------------------------------

def test_loss_and_adapter_gradients_match_reference(weights):
    cfg, model, ad, tr = weights
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 2, 16)), jnp.int32)
    y = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 2, 16)), jnp.int32)
    ad2 = {k: v * (1.0 + 0.1 * i) for i, (k, v) in enumerate(ad.items())}
    prog_tr = as_program(LM.init_trunk(cfg, 3), tr)
    tmpl = LM.init_adapters(cfg, TINY_LORA, 3)
    adx = jax.tree.map(lambda a, b: jnp.stack([a, b]),
                       as_program(tmpl, ad), as_program(tmpl, ad2))
    losses, grads = jax.jit(LM.value_and_grad(cfg, TINY_LORA))(
        adx, prog_tr, x, y)
    grads = dict(LM.named_leaves(grads))
    for c, a in enumerate((ad, ad2)):
        loss, g = jax.value_and_grad(REF.loss_fn)(a, tr, x[c], y[c], model)
        assert float(losses[c]) == pytest.approx(float(loss), rel=1e-5)
        for k in g:
            tol = 1e-4 * float(jnp.abs(g[k]).max())
            np.testing.assert_allclose(grads[k][c], g[k], rtol=1e-3,
                                       atol=tol)


def test_program_leaves_and_initial_weights_are_the_model_files():
    """Adapters flatten in ``leaf_shapes`` order, and the program's
    initial adapters and (bfloat16) trunk equal ``init_params(seed)``."""
    cfg = tiny_cfg("bfloat16")
    model = block(cfg)
    ad_ref, tr_ref = REF.init_params(11, model)
    ad = LM.named_leaves(LM.init_adapters(cfg, TINY_LORA, 11))
    assert [(n, tuple(v.shape)) for n, v in ad] == list(
        REF.leaf_shapes(model).items())
    assert all(np.array_equal(np.asarray(v), np.asarray(ad_ref[n]))
               for n, v in ad)
    tr = dict(LM.named_leaves(LM.init_trunk(cfg, 11)))
    assert set(tr) == set(tr_ref)
    for n, v in tr.items():
        assert v.dtype == jnp.bfloat16 or v.ndim == 1, n
        assert np.array_equal(np.asarray(v, np.float32),
                              np.asarray(tr_ref[n])), n
    assert REF.packed_dim(model) == sum(int(np.prod(v.shape)) for _, v in ad)


def test_published_config_and_the_preset_agree():
    """The benchmark file holds the catalog's numbers (the chip's share
    in the three ``reduced`` keys) and the program's preset is the same
    model: 1,579,008 adapter parameters, 2,048 x 771."""
    model = CONFIG["model"]
    for k, v in model.items():
        if k in CONFIG:
            assert CONFIG[k] == v, k
    assert CONFIG["published"] == {"num_hidden_layers": 27,
                                   "n_routed_experts": 64,
                                   "vocab_size": 102400}
    assert block(DS.FLEET, DS.LORA, seq_len=CONFIG["data"]["seq_len"]) \
        == model
    assert REF.packed_dim(model) == model["packed_params"] == 1579008 \
        == 2048 * 771
    assert CONFIG["sim"]["model_mb"] == pytest.approx(1579008 * 4 / 1e6)
    assert CONFIG["fl"]["model"] == "deepseek-v2-lite"
    y = DS.FLEET.rope_scaling
    assert {k: v for k, v in model["rope_scaling"].items() if k != "type"} \
        == dataclasses.asdict(y)
    assert DS.FLEET.moe.num_experts == 64 and DS.FLEET.moe.top_k == 6
    assert DS.FLEET.d_model == 2048 and DS.FLEET.moe.expert_d_ff == 1408


def test_make_data_gives_shifted_next_tokens():
    """Token data the program trains on: (N, n, S) int32 ids from the
    vocabulary slice, ``y`` being ``x`` shifted by one position, the same
    from the same seed."""
    spec = dict(CONFIG["data"], num_clients=3, n_per_client=5, seq_len=12,
                n_test=4, vocab=50)
    d = REF.make_data(11, spec)
    assert d.x.shape == d.y.shape == (3, 5, 12)
    assert d.test_x.shape == d.test_y.shape == (4, 12)
    assert d.x.dtype == jnp.int32 and d.num_classes == 50
    np.testing.assert_array_equal(d.x[..., 1:], d.y[..., :-1])
    np.testing.assert_array_equal(d.test_x[:, 1:], d.test_y[:, :-1])
    assert 0 <= int(d.x.min()) and int(d.y.max()) < 50
    np.testing.assert_array_equal(REF.make_data(11, spec).x, d.x)


def test_counts_by_hand():
    model = CONFIG["model"]
    macs = REF._token_macs(model, 512)
    # per layer: q 2048x3072, kv_a 2048x576, kv_b 512x4096, o 2048x2048
    proj = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    core = 256.5 * 16 * (192 + 128)
    trunk = 6 * (proj + core) + 2048 * 12800 + 3 * 2048 * 10944 \
        + 5 * (2048 * 64 + 3 * 2048 * 2816 + 3 * 2048 * 1408 * 6 * 8 / 64)
    assert macs["trunk"] == pytest.approx(trunk)
    assert macs["adapters"] == 6 * 16 * (2048 + 3072 + 2048 + 576 + 512
                                         + 4096 + 2048 + 2048)
    assert REF.train_flops(model, 3) == pytest.approx(
        3 * 512 * (4 * (macs["trunk"] + macs["adapters"])
                   + 2 * macs["adapters"]))
    assert REF.eval_flops(model, 2) == pytest.approx(
        2 * 512 * 2 * (macs["trunk"] + macs["adapters"]))


def test_trainer_mfu_by_hand():
    from fleetbench import tracing
    cell = harness.resolve(ROOT, WORKLOAD)
    spec = harness.spec_of(cell)
    mods = [("jit_train_cohort_dyn_offload(1)", 0.0, 2e9),
            ("jit_server_round_step_cohort_offload(5)", 0.0, 6e6)]
    trace = {"ops": [], "modules": mods, "devices": 1, "busy_s": 2.0,
             "window_s": 4.0}
    ctx = tracing.Context(trace, 2, 4.0, spec, "TPU v5 lite",
                          {"transfer_bytes": 0, "completed_steps": 200,
                           "selected": 32})
    flops = REF.train_flops(spec["model"], 200 * 2)
    read = harness.load_reader("trainer_mfu")
    assert read(ctx) == pytest.approx(100 * flops / 2.0 / 197e12)
    trace["modules"] = mods[1:]
    assert read(ctx) is None


# ---------------------------------------------------------------------------
# Through FleetEngine and the harness
# ---------------------------------------------------------------------------

def _register_tiny(dtype):
    cfg = tiny_cfg(dtype)
    FLM.register_model(TINY, lambda: FLM.lora_lm(TINY, cfg, TINY_LORA),
                       allow_override=True)
    return cfg


def tiny_cell(dtype="float32", n=64, x=8):
    """The new cell at N=n, X=x, tiny widths, 16-token sequences."""
    cfg = _register_tiny(dtype)
    cell = tiny(harness.resolve(ROOT, WORKLOAD), n=n, x=x)
    c = cell.config
    c["fl"]["model"] = TINY
    c["model"] = block(cfg)
    c["data"].update(n_per_client=4, seq_len=16, vocab=cfg.vocab_size,
                     n_test=4)
    c["sim"]["model_mb"] = REF.packed_dim(c["model"]) * 4 / 1e6
    return cell


def test_frozen_trunk_is_never_packed_aggregated_or_cached():
    cell = tiny_cell()
    spec = harness.spec_of(cell)
    prog = harness.build(spec, 5, agg_impl="xla", log=lambda m: None)
    eng = prog.engine
    d = REF.packed_dim(spec["model"])
    assert AG.pack_layout(eng._template).dim == d
    assert eng.cache_store.row_bytes == 4 * d
    trunk0 = jax.tree.map(np.asarray, eng._frozen)
    hist = eng.run(prog.policy, rounds=3, diagnostics=False)
    names = [n for n, _ in LM.named_leaves(hist.final_params)]
    assert names == list(REF.leaf_shapes(spec["model"]))
    assert AG.pack_layout(hist.final_params).dim == d
    rows = harness.cached_rows(prog, harness.cached_ids(prog))
    assert rows and all(r.shape == (d,) for r in rows.values())
    same = jax.tree.map(lambda a, b: np.array_equal(a, np.asarray(b)),
                        trunk0, eng._frozen)
    assert all(jax.tree.leaves(same))
    assert hist.acc and all(0.0 <= a <= 1.0 for a in hist.acc)


def test_tiny_cell_runs_to_correct(tmp_path):
    """A tiny copy of the cell through ``run_cell`` on the CPU, judged by
    the cell's own limits: with a float32 trunk the program is the
    reference up to summation order."""
    res = run(tiny_cell(), tmp_path, seed=5)
    assert res["correct"], res["checked"]
    assert res["checked"]["mismatches"]["value"] == 0
    for name in ("update_gap", "change_gap", "cache_gap"):
        assert res["checked"][name]["value"] < 1e-4, name


def test_expert_load_counter_under_telemetry_only():
    """``telemetry="basic"`` adds the held experts' load to the metrics;
    with telemetry off the engine builds no counter."""
    cell = tiny_cell()
    spec = harness.spec_of(cell)
    prog = harness.build(spec, 7, agg_impl="xla", log=lambda m: None)
    eng = prog.engine
    eng.run(prog.policy, rounds=2, diagnostics=False)
    assert eng._load_fn is None
    hist = eng.run(prog.policy, rounds=2, diagnostics=False,
                   telemetry="basic")
    pairs = hist.metrics["moe_routed_pairs"]
    share = hist.metrics["moe_max_expert_share"]
    assert len(pairs) == len(share) == 2
    # the cohort's first batch: X * b sequences of S tokens, top-3 of 16
    # experts, 2 held, over the 2 MoE layers
    x, b, S = spec["fl"]["cohort_size"], spec["sim"]["batch_size"], 16
    assert 0 < pairs[0] <= 2 * x * b * S * 2
    assert 0.5 <= share[0] <= 1.0


@pytest.mark.parametrize("seed", [5, 2718281829, 1414213562])
def test_check_rounds_hold_a_resumed_client(seed, tmp_path):
    """The cell's own fleet (N = 1,024, X = 16) writes cache rows in the
    rounds the check follows, so ``cache_gap`` compares them, but resumes
    no client there; the resume path is compared on the cell's traffic
    cut to N = 16, X = 8, where the check's rounds resume a client and
    the program matches the reference (the model at tiny widths: the
    fleet simulation does not read model values)."""
    from fleetbench import reference
    cell = harness.resolve(ROOT, WORKLOAD)
    spec = harness.spec_of(cell)
    assert spec["fl"]["num_clients"] == 1024
    assert spec["fl"]["clients_per_round"] == 16
    spec["model"] = block(tiny_cfg())
    spec["data"].update(seq_len=16, vocab=tiny_cfg().vocab_size,
                        n_per_client=4)
    k = spec["model_rounds"]
    ref = reference.simulate(spec, REF.make_data(seed, spec["data"]), seed,
                             k, numeric_rounds=k)
    assert ref["cache_after"]

    small = tiny_cell(n=16, x=8)
    spec = harness.spec_of(small)
    ref = reference.simulate(spec, REF.make_data(seed, spec["data"]), seed,
                             k, numeric_rounds=k)
    assert ref["resumed"] > 0
    res = run(small, tmp_path, seed=seed)
    assert res["correct"], res["checked"]
    assert res["checked"]["cache_gap"]["value"] < 1e-4
