"""Invariant auditor: static verification of the engine's round path.

The auditor runs one engine round (``FleetEngine._device_round``, on a
live engine) through a dispatch hook that records every jitted
dispatch, then *lowers and compiles* each one (dynamics step, cache
expiry, cohort index, trainer, round cut, metrics, server step, eval,
plus flude's plan/update jits and the cache stream's expansion and
compaction) and statically checks
the post-SPMD HLO against the round-path contracts
(:mod:`repro.analysis.hlo_checks`):

1. no host callbacks / infeed / outfeed / host-memory copies,
2. donated inputs really alias into outputs,
3. no f64 leakage, fp32-accumulated psum,
4. fleet-shaped (N,)/(X,) operands partitioned on ``("clients",)``,
5. a static per-round ceiling on the cache stream's host transfers
   consistent with ``engine.transfer_stats``.

Run the registered-policy matrix from the CLI (the ``analysis-smoke``
CI job does exactly this, at 8 forced host devices)::

    PYTHONPATH=src python -m repro.analysis.audit --devices 8
    PYTHONPATH=src python -m repro.analysis.audit --policies flude --modes offload

or audit a live engine in tests / notebooks::

    report = audit_engine(engine, "flude")
    report.raise_on_findings()

Lowering traces but executes nothing; the recorded round itself runs
on a toy fleet, so a full matrix audit is seconds, not minutes.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis import hlo_checks as HC


@dataclasses.dataclass(frozen=True)
class _Dispatch:
    """One jitted round-path callable plus the representative arguments
    it is lowered with."""
    name: str
    fn: object
    args: tuple
    min_aliases: int = 0        # expected donated input-output aliases
    sharded: bool = True        # subject to the ("clients",) contract


@dataclasses.dataclass
class AuditReport:
    policy: str
    mode: str                    # "full" | "cohort" | "offload"
    mesh_size: int               # 1 = single-device round path
    dispatches: List[str]
    findings: List[HC.Finding]
    transfer_ceiling: Dict[str, int]

    def ok(self) -> bool:
        return not self.findings

    def summary(self) -> str:
        head = (f"audit[{self.policy}/{self.mode}@{self.mesh_size}d] "
                f"{len(self.dispatches)} dispatches")
        if self.ok():
            return head + ": all contracts hold"
        lines = [head + f": {len(self.findings)} finding(s)"]
        lines += [f"  - {f}" for f in self.findings]
        return "\n".join(lines)

    def raise_on_findings(self) -> None:
        if not self.ok():
            raise AssertionError(self.summary())


def _mode(engine) -> str:
    if engine.cohort is None:
        return "full"
    return "offload" if engine.offload is not None else "cohort"


# ---------------------------------------------------------------------------
# Contract 5: static per-round transfer ceiling
# ---------------------------------------------------------------------------

def transfer_ceiling(engine, uses_cache: bool) -> Dict[str, int]:
    """Static per-round ceiling on the engine's cache-stream transfers.

    The offload stream's steady-state round is at most: one async d2h
    of the cohort index, one of the staged write-back's (X,) arrays and
    one of its compacted written rows (none in a round that wrote no
    row), one async h2d of the fetched hit rows and their positions, a
    pre-issued host read of each d2h copy, and **zero** synchronous
    copies — the contract ``tests/test_cache_store.py`` pins
    dynamically.  Everything else (resident caches, or a policy that
    never caches) moves nothing per round."""
    if engine.offload is None or not uses_cache:
        return {"d2h_async": 0, "h2d_async": 0,
                "pre_issued_reads": 0, "sync_copies": 0}
    return {"d2h_async": 3, "h2d_async": 1,
            "pre_issued_reads": 3, "sync_copies": 0}


def check_transfer_stats(engine, rounds: int, uses_cache: bool,
                         dispatch: str = "cache_stream",
                         ) -> List[HC.Finding]:
    """Compare ``engine.transfer_stats`` after ``rounds`` executed rounds
    against the static ceiling — the dynamic half of contract 5."""
    ceiling = transfer_ceiling(engine, uses_cache)
    stats = engine.transfer_stats
    findings: List[HC.Finding] = []
    for key, per_round in ceiling.items():
        bound = 0 if key == "sync_copies" else per_round * rounds
        got = getattr(stats, key)
        if got > bound:
            findings.append(HC.Finding(
                dispatch, "transfer",
                f"{key}={got} after {rounds} round(s) exceeds the "
                f"static ceiling {bound} "
                f"({per_round}/round) — snapshot: {stats.snapshot()}"))
    return findings


# ---------------------------------------------------------------------------
# One recorded round: every jitted dispatch with its abstract arguments
# ---------------------------------------------------------------------------

def _abstract(tree):
    """Arrays -> ``ShapeDtypeStruct``s, committed ones with their
    shardings (other leaves as they are): what lowering needs, and what
    survives donation."""
    import jax

    def one(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=x.sharding if x.committed else None,
                weak_type=getattr(x, "weak_type", False))
        return x

    return jax.tree.map(one, tree)


def _collect_dispatches(engine, policy, fleet) -> List[_Dispatch]:
    """Run one engine round (``FleetEngine._device_round``) with a hook
    that records each jitted dispatch — its arguments abstracted before
    the call, so that lowering later survives donation — and then runs
    it.  Added to those: what the round reaches only through the policy
    (flude's plan jits) and through the cache stream (its expansion and
    compaction programs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.obs import NULL_TRACER

    out: List[_Dispatch] = []

    def record(name, fn, args, donated_args=0, sharded=True):
        out.append(_Dispatch(
            name, fn, _abstract(args),
            min_aliases=len(jax.tree.leaves(args[:donated_args])),
            sharded=sharded))
        return fn(*args)

    state = policy.init_state()
    global_params = engine._template
    if engine.donate:
        # the server step donates the global model: keep the template
        global_params = jax.tree.map(jnp.copy, global_params)
    caches = engine._fresh_caches(global_params)
    caches_spec = _abstract(caches)
    run, carry = engine._device_run(policy, fleet, state, global_params,
                                    caches, "full", NULL_TRACER)
    rng = jax.random.fold_in(jax.random.key(engine.sim_cfg.seed), 1)
    report, _entry = engine._device_round(run, carry, 0, rng, True,
                                          record)

    plan_jit = getattr(policy, "_plan_jit", None)
    if plan_jit is not None:
        # flude: planning, its fused Eq. 1 update + next plan, and the
        # run-end flush are jitted dispatches of the policy's own
        online = carry.draw.online
        out.append(_Dispatch(
            "flude_plan", plan_jit,
            (state.core, caches_spec, online, rng, policy._hints)))
        last = _abstract(carry.state.last)
        out.append(_Dispatch(
            "flude_update_plan", policy._update_plan_jit,
            (carry.state.core, last, report.received, caches_spec, online,
             rng, policy._hints)))
        out.append(_Dispatch(
            "flude_update", policy._update_jit,
            (carry.state.core, last, report.received)))

    stream = engine._cache_stream
    if stream is not None and policy.uses_cache:
        # the stream's own programs, at their smallest bucket: the
        # expansion of the fetched rows and the compaction of the
        # written ones (the round's receive mask stands in for the
        # server step's write mask: same shape, dtype and placement)
        step = next(d for d in out if d.name == "server_step")
        cache_rows, idx, received_x = (step.args[3], step.args[5],
                                       step.args[8])
        store = stream.store
        b = stream._buckets[0]
        rows = store._treedef.unflatten(
            [jax.ShapeDtypeStruct((b,) + shape, dtype)
             for shape, dtype in zip(store._shapes, store._dtypes)])
        pos = jax.ShapeDtypeStruct((b,), np.int32)
        out.append(_Dispatch("cache_expand", stream.expand_fn,
                             (rows, pos)))
        out.append(_Dispatch("cache_compact", stream.compact_fns[b],
                             (cache_rows, received_x, idx)))
    return out


# ---------------------------------------------------------------------------
# The audit itself
# ---------------------------------------------------------------------------

def _audit_dispatch(d: _Dispatch, mesh_size: int, fleet_dims,
                    ) -> List[HC.Finding]:
    import jax

    lowered = d.fn.lower(*d.args)
    compiled = lowered.compile()
    text = compiled.as_text()
    comps = HC.parse_hlo(text)

    findings: List[HC.Finding] = []
    findings += HC.check_no_host_ops(d.name, text, comps)
    findings += HC.check_no_f64(d.name, text, comps)
    findings += HC.check_psum_dtype(d.name, text, comps)
    if d.min_aliases:
        findings += HC.check_donation(d.name, text, d.min_aliases)
    if mesh_size > 1 and d.sharded:
        findings += HC.check_partition_count(d.name, text, mesh_size)
        leaves = jax.tree.leaves(tuple(d.args))
        # input_shardings[0] mirrors the args pytree with Sharding leaves,
        # minus the arguments XLA pruned as unused (_kept_var_idx holds
        # the flat leaf indices that survive into the executable)
        shardings = jax.tree.leaves(compiled.input_shardings[0])
        kept = getattr(getattr(compiled, "_executable", None),
                       "_kept_var_idx", None)
        if kept is not None and len(shardings) < len(leaves):
            order = sorted(kept)
            if len(order) == len(shardings):
                leaves = [leaves[i] for i in order]
        if len(leaves) == len(shardings):
            findings += HC.check_input_shardings(
                d.name, leaves, shardings, fleet_dims)
        else:
            findings.append(HC.Finding(
                d.name, "sharding",
                f"cannot align {len(leaves)} argument leaves with "
                f"{len(shardings)} compiled input shardings — auditor "
                f"argument replay diverged from the engine"))
    return findings


def audit_engine(engine, policy, fleet=None, *,
                 check_ceiling: bool = True) -> AuditReport:
    """Lower and verify every jitted round-path dispatch of ``engine``
    when driven by ``policy`` (a registered name or a policy instance).
    Returns an :class:`AuditReport`; ``report.raise_on_findings()``
    fails loudly with the dispatch-by-dispatch violations."""
    from repro.fl import Fleet
    from repro.fl.api import make_policy

    if fleet is None:
        fleet = engine._fleet if engine._fleet is not None \
            else Fleet(engine.sim_cfg)
    if isinstance(policy, str):
        policy = make_policy(policy, engine.sim_cfg, engine.fl_cfg,
                             fleet, mesh=engine.mesh)

    mesh_size = 1 if engine.mesh is None else engine.mesh.devices.size
    fleet_dims = {engine.fl_cfg.num_clients}
    if engine.cohort is not None:
        fleet_dims.add(int(engine.cohort))

    dispatches = _collect_dispatches(engine, policy, fleet)
    findings: List[HC.Finding] = []
    for d in dispatches:
        findings += _audit_dispatch(d, mesh_size, fleet_dims)

    ceiling = transfer_ceiling(engine, policy.uses_cache)
    if check_ceiling and ceiling["sync_copies"] != 0:
        findings.append(HC.Finding(
            "cache_stream", "transfer",
            f"static ceiling allows {ceiling['sync_copies']} sync "
            f"copies per round — the double-buffering contract is 0"))

    return AuditReport(policy=policy.name, mode=_mode(engine),
                       mesh_size=mesh_size,
                       dispatches=[d.name for d in dispatches],
                       findings=findings, transfer_ceiling=ceiling)


# ---------------------------------------------------------------------------
# Registered-policy matrix (the analysis-smoke CI entry point)
# ---------------------------------------------------------------------------

#: toy-fleet sizes chosen so no replicated operand's leading dim
#: collides with N or X (model dims 12/24/5, test set 40) — the
#: sharding check can then treat any N/X-leading entry parameter as
#: fleet state
_AUDIT_N = 48
_AUDIT_X = 16


def _build_audited(policy_name: str, mode: str, mesh: Optional[int]):
    from repro.configs.base import FLConfig
    from repro.data.synthetic import federated_classification
    from repro.fl import Fleet, FleetEngine, SimConfig
    from repro.fl.api import make_policy

    N = _AUDIT_N
    data = federated_classification(N, num_classes=5, dim=12,
                                    n_per_client=20, n_test=40, seed=4)
    sim = SimConfig(num_clients=N, rounds=2, local_steps=2, batch_size=8,
                    model_hidden=24, model_depth=1, seed=3)
    kw = dict(num_clients=N, clients_per_round=_AUDIT_X,
              dynamics="markov", donate_buffers=True)
    if mesh is not None and mesh > 1:
        kw["mesh_shape"] = (mesh,)
    if mode in ("cohort", "offload"):
        kw["cohort_size"] = _AUDIT_X
    if mode == "offload":
        kw["cache_offload"] = "host"

    def make(kw):
        fl = FLConfig(**kw)
        engine = FleetEngine(data, sim, fl)
        fleet = Fleet(sim)
        return engine, make_policy(policy_name, sim, fl, fleet,
                                   mesh=engine.mesh), fleet

    engine, policy, fleet = make(kw)
    if engine.cohort is not None \
            and policy.selection_bound() > engine.cohort:
        # select-all policies (mifa, ...) need X = N
        kw["cohort_size"] = N
        engine, policy, fleet = make(kw)
    return engine, policy, fleet


def run_matrix(policies: Optional[Sequence[str]] = None,
               modes: Sequence[str] = ("full", "cohort", "offload"),
               mesh: Optional[int] = None) -> List[AuditReport]:
    """Audit every registered policy's round path in each requested
    mode.  ``mesh=None`` uses all local devices (1 device = unsharded
    audit: contracts 1-3 and 5 still apply)."""
    import jax

    from repro.fl.api import available_policies

    if policies is None:
        policies = available_policies()
    if mesh is None:
        mesh = jax.local_device_count()
    reports = []
    for name in policies:
        for mode in modes:
            engine, policy, fleet = _build_audited(name, mode, mesh)
            reports.append(audit_engine(engine, policy, fleet))
    return reports


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis.audit",
        description="Statically verify the round path's zero-sync, "
                    "donation, dtype, sharding and transfer contracts.")
    parser.add_argument("--policies", nargs="*", default=None,
                        help="registered policy names (default: all)")
    parser.add_argument("--modes", nargs="*",
                        default=("full", "cohort", "offload"),
                        choices=("full", "cohort", "offload"))
    parser.add_argument("--devices", type=int, default=None,
                        help="force this many host platform devices "
                             "(must run before any jax computation)")
    args = parser.parse_args(argv)

    if args.devices is not None:
        from repro.launch.mesh import force_host_platform_device_count
        force_host_platform_device_count(args.devices)

    reports = run_matrix(args.policies, tuple(args.modes))
    bad = 0
    for r in reports:
        print(r.summary())
        bad += len(r.findings)
    print(f"audited {len(reports)} policy/mode combinations, "
          f"{bad} finding(s)")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
