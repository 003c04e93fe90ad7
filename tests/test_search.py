"""Closed-loop auto-strategy search tests (autodist_tpu/search/).

Pins the subsystem's contracts: seeded determinism (identical plan AND
identical dumped trace), budget-bounded termination for both drivers,
mutation validity (every materialized mutation passes ``analysis.verify``
or is counted as pruned), searched-beats-zoo under the shared cost model
on >= 2 bench-family models, the AutoStrategy wiring (search entry in the
ranking, skipped-candidate metadata, all-OOM fallback), trace
reproducibility, and the CLI.
"""
import json
import random

import jax.numpy as jnp
import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.analysis import verify
from autodist_tpu.analysis.diagnostics import Severity
from autodist_tpu.model_item import ModelItem
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.search.drivers import SearchConfig, run_search
from autodist_tpu.search.space import PlanSpace, VarChoice
from autodist_tpu.search.trace import SearchTrace
from autodist_tpu.simulator.simulator import Simulator, _risk_premium
from autodist_tpu.strategy.auto_strategy import (AutoStrategy, Ranking,
                                                 SEARCH_LABEL)


def _emb_item(dense_dim=512, vocab=4096):
    """Embedding + MLP — the sparse/dense mix where per-variable choice
    matters (same fixture family as test_simulator)."""
    params = {"emb": jnp.zeros((vocab, 64)),
              "w1": jnp.zeros((64, dense_dim)),
              "w2": jnp.zeros((dense_dim, 1))}

    def loss_fn(p, batch):
        e = jnp.take(p["emb"], batch["ids"], axis=0)
        h = jnp.tanh(e @ p["w1"])
        return jnp.mean((h @ p["w2"] - batch["y"]) ** 2)

    batch = {"ids": np.zeros((32,), np.int32),
             "y": np.zeros((32, 1), np.float32)}
    return ModelItem(loss_fn=loss_fn, optimizer=optax.adam(1e-3),
                     params=params, example_batch=batch).prepare()


def _mlp_item(width=256, depth=4, batch=64):
    params = {"w%d" % i: jnp.zeros((width, width)) for i in range(depth)}

    def loss_fn(p, b):
        h = b["x"]
        for i in range(depth):
            h = jnp.tanh(h @ p["w%d" % i])
        return jnp.mean(h ** 2)

    batch_np = {"x": np.zeros((batch, width), np.float32)}
    return ModelItem(loss_fn=loss_fn, optimizer=optax.sgd(0.1),
                     params=params, example_batch=batch_np).prepare()


def _spec_2x2():
    """Single-node 4-device spec — the 2x2 CPU mesh of the CI runs."""
    return ResourceSpec.from_dict(
        {"nodes": [{"address": "127.0.0.1", "chief": True, "tpus": 4}]})


def _spec_cluster(n_nodes=4, tpus=4, ici=400):
    nodes = [{"address": "10.0.0.%d" % (i + 1), "tpus": tpus,
              "chief": i == 0, "network_bandwidth": 25}
             for i in range(n_nodes)]
    return ResourceSpec.from_dict(
        {"nodes": nodes, "slice": {"type": "v5e", "ici_bandwidth": ici}})


def _zoo_best_score(item, spec, sim):
    from autodist_tpu.search.scoring import zoo_best
    label, score, _best = zoo_best(item, spec, sim)
    return label, score


# ------------------------------------------------------- determinism


def test_fixed_seed_identical_plan_and_trace():
    """Acceptance: fixed seed => identical chosen plan and identical
    search trace on the 2x2 CPU mesh, for both drivers."""
    item, spec = _emb_item(), _spec_2x2()
    for algo in ("beam", "anneal"):
        cfg = SearchConfig(algo=algo, budget=48, seed=7)
        r1 = run_search(item, spec, config=cfg)
        r2 = run_search(item, spec, config=cfg)
        assert r1.ok and r2.ok
        d1, d2 = r1.strategy.to_dict(), r2.strategy.to_dict()
        d1.pop("id"), d2.pop("id")
        assert d1 == d2, algo
        assert r1.trace.to_dict() == r2.trace.to_dict(), algo


def test_different_seeds_may_walk_differently():
    """Not an equality guarantee — but the rng must actually steer the
    walk: the visit traces of two seeds differ (same model, same
    budget)."""
    item, spec = _emb_item(), _spec_2x2()
    r1 = run_search(item, spec, config=SearchConfig(budget=48, seed=0))
    r2 = run_search(item, spec, config=SearchConfig(budget=48, seed=1))
    ops1 = [e.get("op") for e in r1.trace.entries]
    ops2 = [e.get("op") for e in r2.trace.entries]
    assert ops1 != ops2


# ---------------------------------------------- termination / budget


def test_config_rejects_degenerate_knobs():
    """A beam_width/branch/patience/budget of 0 would silently turn the
    search into a false 'all pruned' run — reject at construction like
    a bad algo name."""
    with pytest.raises(ValueError, match="algo"):
        SearchConfig(algo="bogus")
    for knob in ("budget", "beam_width", "branch", "patience"):
        with pytest.raises(ValueError, match=knob):
            SearchConfig(**{knob: 0})


@pytest.mark.parametrize("algo", ["beam", "anneal", "both"])
def test_terminates_within_candidate_budget(algo):
    item, spec = _emb_item(), _spec_2x2()
    budget = 32
    r = run_search(item, spec, config=SearchConfig(algo=algo,
                                                   budget=budget))
    assert r.ok
    assert r.candidates <= budget
    assert len(r.trace.scored()) == r.candidates
    # the chosen plan is at least as good as every seed the run scored
    seed_scores = [e["score_ms"] for e in r.trace.scored()
                   if e["algo"] == "seed" and "score_ms" in e]
    assert seed_scores
    # trace scores are ms rounded to 6 places; compare on that grid
    assert round(r.record.score_s * 1e3, 6) <= min(seed_scores) + 1e-9


# ------------------------------------------------- mutation validity


def test_mutations_always_verify_or_are_pruned():
    """Acceptance: mutation operators always produce plans that pass
    ``analysis.verify()`` (the space is constrained by construction) —
    and the scorer accounts every candidate as scored-or-pruned."""
    item, spec = _emb_item(), _spec_cluster()
    space = PlanSpace(item, spec)
    rng = random.Random(0)
    frontier = [plan for _, plan in space.seeds()]
    checked = 0
    for _ in range(120):
        plan = frontier[rng.randrange(len(frontier))]
        mut = space.mutate(plan, rng)
        if mut is None:
            continue
        child, op = mut
        strategy = space.build(child)
        errs = [d for d in verify(strategy, item, spec)
                if d.severity >= Severity.ERROR]
        assert not errs, (op, [d.format() for d in errs])
        frontier.append(child)
        checked += 1
    assert checked >= 60  # the walk genuinely explored


def test_a_wire_bound_search_returns_a_plan_priced_with_its_whole_exchange():
    """Where the wire dominates (4 x 2048^2 float32 gradients over a
    10 Gbit/s interconnect) the searcher returns a plan the lowering runs
    as the cost model priced it: the step time holds the plan's whole
    gradient exchange, and neither the plan nor the built strategy
    carries a switch the lowering does not read."""
    item, spec = _mlp_item(2048, 4, 2048), _spec_cluster(2, 4, ici=10)
    result = run_search(item, spec, SearchConfig(budget=96))
    bd = result.record.breakdown
    assert bd.allreduce_s > bd.compute_s > 0.0  # wire-bound, and on the wire
    assert bd.step_time_s == pytest.approx(
        bd.compute_s + bd.allreduce_s + bd.ps_s + bd.mp_s + bd.latency_s,
        rel=1e-12)
    assert "overlap" not in result.plan.describe()
    assert "overlap" not in result.strategy.graph_config.to_dict()
    assert not [f for f in vars(bd) if "overlap" in f]
    # the built strategy prices the same without the searcher around it
    again = Simulator(item, spec).simulate(result.strategy, "again").breakdown
    assert again.step_time_s == pytest.approx(bd.step_time_s, rel=1e-12)


def test_scorer_accounts_scored_plus_pruned():
    item, spec = _emb_item(), _spec_2x2()
    # absurd capacity: every candidate projects OOM -> all pruned
    r = run_search(item, spec, config=SearchConfig(budget=16),
                   hbm_capacity_bytes=1.0)
    assert not r.ok
    assert r.pruned == r.candidates > 0
    assert r.trace.prune_reasons() == {"oom:ADT501": r.candidates}
    assert r.trace.result["chosen"] is None


def test_sparse_vars_never_partition_onto_dense_allreduce():
    """The ADT309 hazard (reduce-scatter densifying a row-sparse
    gradient) is excluded from the space by construction."""
    item, spec = _emb_item(), _spec_cluster()
    space = PlanSpace(item, spec)
    c = space.canon(VarChoice(sync="AllReduce", shards=4, axis=0), "emb")
    assert c.shards == 1
    rng = random.Random(3)
    plan = space.seeds()[0][1]
    for _ in range(200):
        mut = space.mutate(plan, rng)
        if mut is None:
            continue
        plan = mut[0]
        for name, choice in plan.choices:
            if space.infos[name].sparse and choice.sync == "AllReduce":
                assert choice.shards == 1, (name, choice)


# ------------------------------------- searched vs zoo (acceptance)


@pytest.mark.parametrize("make_item,spec_fn", [
    (_emb_item, _spec_cluster),   # bert/dlrm-family: sparse + dense mix
    (_mlp_item, _spec_cluster),   # resnet-family: dense stacks
])
def test_searched_plan_beats_or_matches_zoo(make_item, spec_fn):
    """Acceptance: on >= 2 bench-family models the searched per-variable
    strategy scores <= the best hand-picked zoo strategy under the SAME
    calibrated cost model, is chosen without compiling anything, and the
    chosen plan passes verify() and the ADT501 gate."""
    item, spec = make_item(), spec_fn()
    sim = Simulator(item, spec)
    r = run_search(item, spec, config=SearchConfig(budget=64),
                   simulator=sim)
    assert r.ok
    zoo_label, zoo_score = _zoo_best_score(item, spec, sim)
    assert r.record.score_s <= zoo_score + 1e-12, (
        r.record.score_s, zoo_label, zoo_score)
    errs = [d for d in verify(r.strategy, item, spec)
            if d.severity >= Severity.ERROR]
    assert not errs
    from autodist_tpu.analysis.memory import budget_diagnostics
    assert not [d for d in budget_diagnostics(
        r.record.breakdown.hbm_bytes, r.record.breakdown.hbm_capacity,
        source="plan-level") if d.code == "ADT501"]


def test_search_smoke_small_budget_lints_clean():
    """CI tier-1-fast smoke: a tight-budget search on one small model
    still produces a plan with zero ADT errors."""
    item, spec = _mlp_item(width=64, depth=2, batch=16), _spec_2x2()
    r = run_search(item, spec, config=SearchConfig(budget=20))
    assert r.ok and r.candidates <= 20
    assert not [d for d in verify(r.strategy, item, spec)
                if d.severity >= Severity.ERROR]


# ------------------------------------------------ trace reproducibility


def test_trace_dump_reproduces_run(tmp_path):
    """Acceptance: search runs are reproducible from the dumped trace —
    its header carries the full SearchConfig; re-running yields the same
    chosen plan and score."""
    item, spec = _emb_item(), _spec_2x2()
    path = str(tmp_path / "trace.json")
    cfg = SearchConfig(algo="both", budget=40, seed=11)
    r1 = run_search(item, spec, config=cfg, trace_path=path)
    loaded = SearchTrace.load(path)
    assert loaded.to_dict() == r1.trace.to_dict()
    cfg2 = SearchConfig.from_dict(loaded.header["config"])
    assert cfg2 == cfg
    r2 = run_search(item, spec, config=cfg2)
    assert r2.trace.result == loaded.result
    d1, d2 = r1.strategy.to_dict(), r2.strategy.to_dict()
    d1.pop("id"), d2.pop("id")
    assert d1 == d2


# ------------------------------------------------- AutoStrategy wiring


def test_autostrategy_ranks_search_entry_and_picks_at_least_zoo():
    item, spec = _emb_item(), _spec_cluster()
    auto = AutoStrategy()
    chosen = auto.build(item, spec)
    assert isinstance(auto.last_ranking, Ranking)
    labels = [r.label for r in auto.last_ranking]
    assert SEARCH_LABEL in labels
    best = auto.last_ranking[0]
    zoo_scores = [r.step_time_s * _risk_premium(r.strategy)
                  for r in auto.last_ranking if r.label != SEARCH_LABEL]
    assert (best.step_time_s * _risk_premium(best.strategy)
            <= min(zoo_scores) + 1e-12)
    assert auto.last_ranking.search_trace is not None
    assert auto.last_ranking.search_trace.result["candidates"] > 0
    # the chosen plan still verifies clean against the real inputs
    assert not [d for d in verify(chosen, item, spec)
                if d.severity >= Severity.ERROR]


def test_autostrategy_search_off_keeps_zoo_only():
    item, spec = _emb_item(), _spec_cluster()
    auto = AutoStrategy(search=False)
    auto.build(item, spec)
    assert SEARCH_LABEL not in [r.label for r in auto.last_ranking]
    assert auto.last_ranking.search_trace is None


def test_autostrategy_records_skipped_candidates(caplog):
    """Satellite: builder failures log at WARNING (with the ADT
    diagnostic when present) and land on last_ranking.skipped."""
    import logging as pylogging

    from autodist_tpu.analysis.diagnostics import DiagnosticError, error
    from autodist_tpu.strategy.base import StrategyBuilder
    from autodist_tpu.utils.logging import get_logger

    class _Boom(StrategyBuilder):
        def build(self, model_item, resource_spec):
            raise DiagnosticError(error(
                "ADT301", "synthetic builder failure", var="w1"))

    item, spec = _emb_item(), _spec_cluster()
    auto = AutoStrategy(search=False,
                        extra_candidates=[("boom", _Boom())])
    logger = get_logger()
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(pylogging.WARNING, logger="autodist_tpu"):
            auto.build(item, spec)
    finally:
        logger.removeHandler(caplog.handler)
    assert auto.last_ranking.skipped == [
        {"label": "boom",
         "reason": auto.last_ranking.skipped[0]["reason"]}]
    assert "ADT301" in auto.last_ranking.skipped[0]["reason"]
    warnings = [r.getMessage() for r in caplog.records
                if r.levelno >= pylogging.WARNING]
    assert any("candidate boom failed" in m and "ADT301" in m
               for m in warnings)


def test_autostrategy_all_oom_fallback(caplog):
    """Satellite: when EVERY candidate (zoo and searched) projects OOM,
    the skip path falls back to the unskipped ranking and AutoStrategy
    still returns a plan instead of raising."""
    import logging as pylogging

    from autodist_tpu.utils.logging import get_logger
    item, spec = _emb_item(), _spec_cluster()
    auto = AutoStrategy(hbm_capacity_bytes=1.0)
    logger = get_logger()
    logger.addHandler(caplog.handler)
    try:
        with caplog.at_level(pylogging.INFO, logger="autodist_tpu"):
            chosen = auto.build(item, spec)
    finally:
        logger.removeHandler(caplog.handler)
    assert chosen is not None
    assert len(auto.last_ranking) > 0
    assert not auto.last_ranking[0].breakdown.feasible
    msgs = [r.getMessage() for r in caplog.records]
    assert any("every candidate is projected to OOM" in m for m in msgs)


def test_autostrategy_still_trains_end_to_end():
    """The searched plan must lower and train through the full stack."""
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(16, 4).astype(np.float32))}
    loss = lambda p, b: jnp.mean((b["x"] @ p["w"] - b["y"]) ** 2)  # noqa: E731
    batch = {"x": rng.randn(16, 16).astype(np.float32),
             "y": rng.randn(16, 4).astype(np.float32)}
    builder = AutoStrategy(search=SearchConfig(budget=32))
    ad = autodist_tpu.AutoDist(strategy_builder=builder)
    step = ad.function(loss, optimizer=optax.sgd(0.1), params=params)
    losses = [step(batch)["loss"] for _ in range(5)]
    assert losses[-1] < losses[0]
    assert SEARCH_LABEL in [r.label for r in builder.last_ranking]
    autodist_tpu.reset()


# -------------------------------------------------------- telemetry


def test_search_telemetry_counters():
    from autodist_tpu.telemetry import spans as tel
    rec = tel.get_recorder()
    before = rec.counters().get("search.candidates", 0.0)
    item, spec = _mlp_item(width=64, depth=2, batch=16), _spec_2x2()
    r = run_search(item, spec, config=SearchConfig(budget=16))
    after = rec.counters().get("search.candidates", 0.0)
    assert after - before == r.candidates
    assert rec.gauges().get("search.candidates_per_s", 0.0) > 0


# --------------------------------------------------------------- CLI


def test_cli_json_trace_and_plan(tmp_path, capsys):
    from autodist_tpu.search import cli
    trace = tmp_path / "trace.json"
    plan = tmp_path / "plan.json"
    rc = cli.main(["linear_regression", "--budget", "16", "--seed", "1",
                   "--format", "json", "--trace-out", str(trace),
                   "--dump-plan", str(plan)])
    out = capsys.readouterr().out
    assert rc == 0
    doc = json.loads(out)
    assert doc["chosen"]
    assert doc["verify_errors"] == 0
    assert doc["candidates"] <= 16
    assert doc["beats_zoo"] is True
    assert SearchTrace.load(str(trace)).result["chosen"]
    from autodist_tpu.strategy.base import Strategy
    loaded = Strategy.deserialize(path=str(plan))
    assert loaded.node_config


def test_cli_unknown_example_exit_2(capsys):
    from autodist_tpu.search import cli
    assert cli.main(["nope"]) == 2
