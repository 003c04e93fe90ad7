"""Continuous-batching decode: KV-cache slot engine correctness.

The decode tentpole's contracts (``serving/decode.py``, docs/serving.md
"Continuous batching"):

- **exact parity**: greedy decode through the slot engine — prefill
  seeding the cache, cache-carried steps, eviction and readmission
  mid-flight — produces token-for-token what full-sequence recompute
  produces, for an AllReduce AND a PS-backed strategy;
- **mask identity**: a padded/dead slot's cache garbage never leaks
  into a live slot's attention (``ops.attention.cached_attention``
  masks rows past the cursor), so slot reuse needs no zeroing;
- **flash decode parity**: the pallas inner loop matches the reference
  cached attention to fp32 tolerance (2e-5 documented — the kernel's
  blocked online softmax reassociates the reduction);
- **zero recompiles after warmup**: one decode-step program serves
  every occupancy — admissions and evictions never grow a jit cache;
- **drain semantics**: in-flight sequences decode to completion,
  queued requests shed typed with a populated ``retry_after_s``.
"""
import time

import numpy as np
import optax
import pytest

import autodist_tpu
from autodist_tpu import strategy as S
from autodist_tpu.models import lm
from autodist_tpu.ops.attention import (cached_attention,
                                        flash_cached_attention,
                                        reference_attention)
from autodist_tpu.serving import ServingUnavailable
from autodist_tpu.serving.decode import (DecodeConfig, DecodeEngine,
                                         SlotScheduler)


# ------------------------------------------------------------- scheduler


class TestSlotScheduler:
    def test_continuous_admits_into_any_freed_slot(self):
        sched = SlotScheduler(4)
        assert sched.admissible(queued=10) == 4
        sched.occupy(0, object())
        sched.occupy(2, object())
        assert sched.free_slots() == [1, 3]
        assert sched.admissible(queued=10) == 2
        assert sched.admissible(queued=1) == 1
        assert sched.occupancy() == 0.5

    def test_evict_frees_for_readmission(self):
        sched = SlotScheduler(2)
        a, b = object(), object()
        sched.occupy(0, a)
        sched.occupy(1, b)
        assert sched.admissible(queued=5) == 0
        assert sched.evict(0) is a
        assert sched.get(0) is None
        assert sched.get(1) is b
        assert sched.live_slots() == [1]
        c = object()
        sched.occupy(0, c)
        assert sched.get(0) is c

    def test_config_validation(self):
        with pytest.raises(ValueError):
            DecodeConfig(slots=0)
        with pytest.raises(ValueError):
            DecodeConfig(max_new_tokens=0)


# ----------------------------------------------------- cached attention


def _rand_cache(rng, b, t, h, d):
    k = rng.randn(b, t, h, d).astype(np.float32)
    v = rng.randn(b, t, h, d).astype(np.float32)
    return k, v


def test_cached_attention_matches_reference_on_live_prefix():
    """Decode-shape attention == full attention restricted to the rows
    at/below the cursor, per example (exact — same fp32 softmax)."""
    rng = np.random.RandomState(0)
    b, t, h, d = 4, 32, 2, 8
    q = rng.randn(b, h, d).astype(np.float32)
    k, v = _rand_cache(rng, b, t, h, d)
    cursor = np.array([0, 5, 17, 31], np.int32)
    out = np.asarray(cached_attention(q, k, v, cursor))
    for i in range(b):
        c = int(cursor[i]) + 1
        ref = np.asarray(reference_attention(
            q[i:i + 1, None], k[i:i + 1, :c], v[i:i + 1, :c]))[:, 0]
        np.testing.assert_allclose(out[i:i + 1], ref, rtol=1e-6, atol=1e-6)


def test_cached_attention_masks_dead_rows():
    """Rows past the cursor are evicted sequences' garbage: scrambling
    them must not change a single output bit — the property that makes
    slot reuse safe without zeroing the cache."""
    rng = np.random.RandomState(1)
    b, t, h, d = 3, 16, 2, 4
    q = rng.randn(b, h, d).astype(np.float32)
    k, v = _rand_cache(rng, b, t, h, d)
    cursor = np.array([2, 7, 15], np.int32)
    base = np.asarray(cached_attention(q, k, v, cursor))
    k2, v2 = k.copy(), v.copy()
    for i in range(b):
        c = int(cursor[i]) + 1
        # evicted sequences leave real (finite) stale values behind —
        # scramble them hugely; the masked weights underflow to exact
        # zero so the products vanish bit-exactly
        k2[i, c:] = 1e6 * rng.randn(t - c, h, d)
        v2[i, c:] = -1e6 * rng.randn(t - c, h, d)
    out = np.asarray(cached_attention(q, k2, v2, cursor))
    np.testing.assert_array_equal(base, out)


def test_flash_cached_attention_parity():
    """The pallas flash inner loop vs the reference cached attention.
    Tolerance 2e-5 (documented): the blocked online softmax
    reassociates the fp32 reduction — observed error is ~1e-7, the
    bound leaves headroom for other backends' accumulation order."""
    rng = np.random.RandomState(2)
    b, t, h, d = 4, 64, 2, 16
    q = rng.randn(b, h, d).astype(np.float32)
    k, v = _rand_cache(rng, b, t, h, d)
    cursor = np.array([0, 5, 31, 63], np.int32)
    ref = np.asarray(cached_attention(q, k, v, cursor))
    out = np.asarray(flash_cached_attention(q, k, v, cursor))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


# ------------------------------------------- model-level decode parity


def _reference_tokens(apply_fn, params, prompt, max_new, eos_id=None):
    """Greedy generation by full-sequence recompute — the ground truth
    the cached decode path must match token for token."""
    ids = list(map(int, prompt))
    out = []
    for _ in range(max_new):
        logits = np.asarray(apply_fn(params, np.asarray([ids], np.int32)))
        nxt = int(np.argmax(logits[0, -1]))
        out.append(nxt)
        ids.append(nxt)
        if eos_id is not None and nxt == eos_id:
            break
    return out


def test_prefill_decode_step_parity_pure_model():
    """prefill + cached decode_step == full recompute, straight through
    ``model.apply`` (no engine, no mesh): localizes cursor/cache bugs
    away from the distribution machinery."""
    import jax
    import jax.numpy as jnp

    cfg = lm.LMConfig.tiny()
    _, params, _, apply_fn = lm.make_train_setup(cfg, seq_len=16,
                                                 batch_size=4)
    setup = lm.make_decode_setup(cfg)
    prompts = [[5, 9], [17, 3, 21, 8], [1]]
    plen = np.array([len(p) for p in prompts], np.int32)
    pad = max(len(p) for p in prompts)
    toks = np.zeros((len(prompts), pad), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    pre = setup.prefill_fn(params, {"tokens": jnp.asarray(toks),
                                    "length": jnp.asarray(plen)})
    dstate = setup.init_dstate(len(prompts))
    dstate["k"] = np.asarray(pre["k"])
    dstate["v"] = np.asarray(pre["v"])
    dstate["token"] = np.asarray(pre["next_token"])
    dstate["cursor"] = plen.copy()
    dstate["alive"] = np.ones(len(prompts), np.bool_)
    generated = [[int(t)] for t in dstate["token"]]
    step = jax.jit(setup.decode_fn)
    for _ in range(5):
        out = step(params, dstate)
        nxt = np.asarray(out["next_token"])
        dstate["k"], dstate["v"] = out["k"], out["v"]
        dstate["token"] = nxt
        dstate["cursor"] = dstate["cursor"] + 1
        for i in range(len(prompts)):
            generated[i].append(int(nxt[i]))
    for i, p in enumerate(prompts):
        ref = _reference_tokens(apply_fn, params, p, 6)
        assert generated[i] == ref, (
            "slot %d diverged: cached %s vs recompute %s"
            % (i, generated[i], ref))


# --------------------------------------------------- engine end to end


def _build_lm_runner(make_builder, train_steps=1):
    cfg = lm.LMConfig.tiny()
    loss_fn, params, batch, apply_fn = lm.make_train_setup(
        cfg, seq_len=16, batch_size=8)
    autodist_tpu.reset()
    ad = autodist_tpu.AutoDist(strategy_builder=make_builder())
    runner = ad.build(loss_fn, optax.adam(1e-3), params, batch)
    runner.init(params)
    for _ in range(train_steps):
        runner.run(batch)  # decode params that actually moved
    return runner, cfg, apply_fn


def test_engine_parity_eviction_readmission_allreduce():
    """The whole slot engine against full recompute: 12 overlapping
    requests through 8 slots (so sequences evict and new ones are
    admitted mid-flight), mixed prompt lengths and generation caps, an
    EOS stop, a done-at-admission request — every returned sequence
    must equal the reference token for token, with ZERO recompiles
    after warmup."""
    runner, cfg, apply_fn = _build_lm_runner(S.AllReduce)
    params = runner.gather_params()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, cfg.vocab_size, (1 + i % 6,)).astype(np.int32)
               for i in range(12)]
    caps = [3 + (i * 3) % 8 for i in range(12)]
    caps[5] = 1  # satisfied by its prefill alone — never occupies a slot
    raw = [_reference_tokens(apply_fn, params, p, m)
           for p, m in zip(prompts, caps)]
    # an eos_id drawn from a reference stream: sequence 0 must stop
    # early with finished="eos"; any other sequence hitting it must too
    eos_id = raw[0][2]
    expected = []
    for toks in raw:
        cut = toks.index(eos_id) + 1 if eos_id in toks else len(toks)
        expected.append(toks[:cut])

    engine = DecodeEngine(runner, lm.make_decode_setup(cfg),
                          DecodeConfig(slots=8, max_new_tokens=8,
                                       prefill_len=8, eos_id=eos_id))
    try:
        engine.warmup()
        futures = [engine.submit(p, max_new_tokens=m)
                   for p, m in zip(prompts, caps)]
        results = [f.result(timeout=120) for f in futures]
        for i, (r, exp) in enumerate(zip(results, expected)):
            assert list(map(int, r["tokens"])) == exp, (
                "sequence %d diverged: engine %s vs recompute %s"
                % (i, list(map(int, r["tokens"])), exp))
            want = "eos" if exp[-1] == eos_id else "length"
            assert r["finished"] == want
            assert r["prompt_len"] == len(prompts[i])
        assert results[0]["finished"] == "eos"  # stopped at the EOS
        assert len(results[5]["tokens"]) == 1   # done at admission
        stats = engine.stats()
        assert stats["recompiles_after_warmup"] == 0, stats
        assert stats["completed"] == 12
        assert stats["evictions"] == 12
        assert stats["errors"] == 0
        assert stats["peak_occupancy"] > 0
        # the prefill program's shape is fixed: over-long prompts are
        # rejected synchronously, not silently truncated
        with pytest.raises(ValueError, match="prompt length"):
            engine.submit(np.zeros(9, np.int32))
    finally:
        engine.close()


def test_engine_parity_ps():
    """Same parity contract on a host-PS strategy: the decode step
    gathers PS-resident params through the shared prefill snapshot."""
    runner, cfg, apply_fn = _build_lm_runner(S.PS)
    params = runner.gather_params()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, cfg.vocab_size, (2 + i,)).astype(np.int32)
               for i in range(4)]
    engine = DecodeEngine(runner, lm.make_decode_setup(cfg),
                          DecodeConfig(slots=8, max_new_tokens=6,
                                       prefill_len=8))
    try:
        engine.warmup()
        results = [engine.generate(p, timeout=120) for p in prompts]
        for p, r in zip(prompts, results):
            ref = _reference_tokens(apply_fn, params, p, 6)
            assert list(map(int, r["tokens"])) == ref
        assert engine.recompiles_after_warmup() == 0
    finally:
        engine.close()


def test_a_mixed_length_trace_on_host_ps_refills_freed_slots_with_no_recompile():
    """Six requests a slot, one long generation among shorts in every
    group of eight, all queued at once on a host-PS runner: every
    sequence runs to its own length, none errors, the one decode program
    serves every occupancy, and freed slots take queued work while a long
    sequence still runs (a batch drained before it is refilled would take
    ``groups * (longest - 1)`` steps)."""
    runner, cfg, _ = _build_lm_runner(S.PS)
    slots, groups, longest, short = 8, 6, 48, 8
    rng = np.random.RandomState(7)
    trace = [(rng.randint(0, cfg.vocab_size, (1 + i % 6,)).astype(np.int32),
              longest if i % slots == 0 else short)
             for i in range(groups * slots)]
    engine = DecodeEngine(runner, lm.make_decode_setup(cfg),
                          DecodeConfig(slots=slots, max_new_tokens=longest,
                                       prefill_len=8))
    try:
        engine.warmup()
        futures = [engine.submit(p, max_new_tokens=m) for p, m in trace]
        lengths = [len(f.result(timeout=300)["tokens"]) for f in futures]
        stats = engine.stats()
    finally:
        engine.close()
    assert lengths == [m for _, m in trace]
    assert stats["recompiles_after_warmup"] == 0, stats
    assert stats["errors"] == 0
    assert stats["completed"] == stats["evictions"] == len(trace)
    assert 0 < stats["steps"] < groups * (longest - 1), stats["steps"]


def test_drain_completes_in_flight_and_sheds_queued():
    """Planned departure: sequences already in slots decode to
    completion and resolve normally; everything still queued sheds
    typed with the drain's Retry-After; later submits shed
    synchronously."""
    runner, cfg, _ = _build_lm_runner(S.PS, train_steps=0)
    rng = np.random.RandomState(5)
    engine = DecodeEngine(runner, lm.make_decode_setup(cfg),
                          DecodeConfig(slots=8, max_new_tokens=48,
                                       prefill_len=8))
    engine.warmup()
    first = [engine.submit(rng.randint(0, cfg.vocab_size, (4,))
                           .astype(np.int32)) for _ in range(8)]
    # wait until ALL EIGHT are in slots: the drain below must catch them
    # in flight, not still queued (48-token sequences stay live for far
    # longer than this poll)
    deadline = time.perf_counter() + 30
    while len(engine.scheduler.live_slots()) < 8:
        assert time.perf_counter() < deadline, "admission never happened"
        time.sleep(0.005)
    queued = [engine.submit(rng.randint(0, cfg.vocab_size, (4,))
                            .astype(np.int32)) for _ in range(8)]
    shed = engine.drain(retry_after_s=1.25)
    assert shed >= 1, "every queued request was somehow admitted"
    completed = 0
    for f in first:
        out = f.result(timeout=120)  # in-flight ran to completion
        assert len(out["tokens"]) == 48
        completed += 1
    assert completed == 8
    for f in queued:
        try:
            out = f.result(timeout=120)
            # admitted into a freed slot before the drain landed — must
            # then have completed fully
            assert len(out["tokens"]) == 48
        except ServingUnavailable as e:
            assert e.retry_after_s == 1.25
    with pytest.raises(ServingUnavailable) as ei:
        engine.submit(np.array([1], np.int32))
    assert ei.value.retry_after_s == 1.25
    assert engine.stats()["shed"] == shed
    engine.close()  # idempotent


# ------------------------------------------------------------ ADT442


def test_verify_decode_hbm_lint():
    from autodist_tpu.analysis import rules
    from autodist_tpu.analysis.memory import GIB

    diags = rules.verify_decode(16 * GIB, param_bytes=1 * GIB,
                                slots=64, max_len=2048, replicas=1,
                                budget_bytes=8 * GIB)
    assert [d.code for d in diags] == ["ADT442"]
    assert diags[0].severity.name == "WARNING"
    assert "64 slots x 2048 max_len" in diags[0].message
    assert "shrink slots" in diags[0].fixit
    # the slot dim shards over replicas: the same cache fits at 4
    assert rules.verify_decode(16 * GIB, param_bytes=1 * GIB,
                               replicas=4, budget_bytes=8 * GIB) == []
    # no budget configured -> nothing to project against, no noise
    assert rules.verify_decode(16 * GIB, param_bytes=1 * GIB) == []


# ------------------------------------------- batcher queue-age (sat.)


class _StubEngine:
    """The engine surface MicroBatcher touches, with a blockable
    dispatch — models a worker parked inside a long program call, the
    exact regime the queue-age floor exists for."""

    def __init__(self, release):
        from autodist_tpu.serving import ServingConfig
        self.config = ServingConfig(buckets=(1,), max_delay_ms=1.0,
                                    max_queue=2)
        self.max_batch = 1
        self.buckets = (1,)
        self.stats = {}
        self.entered = __import__("threading").Event()
        self._release = release

    def run_batch(self, feeds):
        self.entered.set()
        self._release.wait(timeout=30)
        return {"y": np.zeros((len(feeds), 1), np.float32)}, len(feeds)

    def fan_out(self, fetched, n):
        for i in range(n):
            yield {"y": fetched["y"][i]}

    def recompiles_after_warmup(self):
        return 0


def test_batcher_queue_age_floors_retry_after():
    """The head-of-line queue age is reported in ``stats()`` and FLOORS
    the computed Retry-After: a request that has already waited T
    seconds proves the tier clears slower than the drain-rate EWMA
    claims, so the hint must not promise anything sooner."""
    import threading

    from autodist_tpu.serving import MicroBatcher

    release = threading.Event()
    engine = _StubEngine(release)
    mb = MicroBatcher(engine)
    try:
        held = mb.submit({"x": np.zeros(1)})  # worker parks in dispatch
        assert engine.entered.wait(timeout=10)
        q1 = mb.submit({"x": np.zeros(1)})
        q2 = mb.submit({"x": np.zeros(1)})
        time.sleep(0.25)
        age = mb.stats()["oldest_queue_age_s"]
        assert age is not None and age >= 0.2
        # a huge measured drain rate would otherwise quote ~0s back-off
        mb._drain_rate = 1e6
        with pytest.raises(ServingUnavailable) as ei:
            mb.submit({"x": np.zeros(1)})
        assert ei.value.retry_after_s >= 0.2
    finally:
        release.set()
        for f in (held, q1, q2):
            try:
                f.result(timeout=10)
            except ServingUnavailable:
                pass  # shed at close is fine; hanging is not
        mb.close()
    assert mb.stats()["oldest_queue_age_s"] is None
