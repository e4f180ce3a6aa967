"""MegaRoute: traffic generators (bursty MMPP / diurnal), placement +
SLO-admission policies shared between the offline ``router_workload``
evaluator and the live ``Router``, disaggregated prefill/decode KV
migration, chunked prefill, and the router-vs-single-engine greedy
token-identity oracles."""

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.core.simkit.engine import Engine
from repro.core.simkit.workload import (
    PlacementView,
    ServeProfile,
    admission_decision,
    bursty_requests,
    diurnal_requests,
    place,
    poisson_requests,
    router_summary,
    router_workload,
)
from repro.models import get_model
from repro.serve import (
    MegaServe,
    PagedKVCache,
    PoolSpec,
    Request,
    Router,
    RouterConfig,
    Scheduler,
    ServeConfig,
)

# ----------------------------------------------------- traffic generators ---


def test_bursty_requests_deterministic_and_overdispersed():
    a = bursty_requests(300, 40.0, prompt_lens=(16, 32), seed=7)
    b = bursty_requests(300, 40.0, prompt_lens=(16, 32), seed=7)
    assert [(r.rid, r.arrival, r.prompt_len, r.max_new) for r in a] == \
           [(r.rid, r.arrival, r.prompt_len, r.max_new) for r in b]
    assert len(a) == 300
    arr = np.array([r.arrival for r in a])
    assert (np.diff(arr) >= 0).all()
    # MMPP interarrivals are overdispersed vs Poisson: CV > 1
    gaps = np.diff(arr)
    cv = gaps.std() / gaps.mean()
    assert cv > 1.1, cv
    # and a different seed moves the arrivals
    c = bursty_requests(300, 40.0, prompt_lens=(16, 32), seed=8)
    assert [r.arrival for r in c] != [r.arrival for r in a]


def test_bursty_requests_validates_burst_shape():
    with pytest.raises(ValueError):
        bursty_requests(10, 10.0, burst_mult=1.0)
    with pytest.raises(ValueError):
        bursty_requests(10, 10.0, burst_frac=0.0)
    with pytest.raises(ValueError):
        bursty_requests(10, 10.0, burst_frac=1.5)


def test_diurnal_requests_follow_sinusoid_envelope():
    period = 4.0
    reqs = diurnal_requests(
        2000, 50.0, period_s=period, depth=0.9, prompt_lens=(16,), seed=3
    )
    assert len(reqs) == 2000
    arr = np.array([r.arrival for r in reqs])
    assert (np.diff(arr) >= 0).all()
    # phase-fold: sin > 0 on the first half-period, so the peak half must
    # hold well over half the arrivals
    phase = (arr % period) / period
    peak = (phase < 0.5).mean()
    assert peak > 0.6, peak
    again = diurnal_requests(
        2000, 50.0, period_s=period, depth=0.9, prompt_lens=(16,), seed=3
    )
    assert [r.arrival for r in again] == [r.arrival for r in reqs]
    with pytest.raises(ValueError):
        diurnal_requests(10, 10.0, depth=0.0)
    with pytest.raises(ValueError):
        diurnal_requests(10, 10.0, depth=1.2)


# ------------------------------------------- placement + admission policies ---


def _views():
    return [
        PlacementView(queued=4, queued_prefill_tokens=256, active=4,
                      kv_used_frac=0.9),
        PlacementView(queued=0, queued_prefill_tokens=0, active=1,
                      kv_used_frac=0.1),
    ]


def test_placement_policies_pick_expected_replica():
    views = _views()
    assert place("round_robin", views, rr=0) == 0
    assert place("round_robin", views, rr=3) == 1
    assert place("least_kv", views) == 1
    assert place("jsq", views) == 1
    with pytest.raises(ValueError):
        place("warmest", views)


def test_admission_decision_admit_redirect_shed():
    views = _views()
    prof = ServeProfile()
    # no SLO: the policy's pick stands even when loaded
    act, rep, _ = admission_decision("round_robin", views, 64, rr=0,
                                     prof=prof, slo_ttft_s=0.0)
    assert (act, rep) == ("admit", 0)
    # tight SLO: replica 0 busts it, replica 1 does not -> redirect
    est1 = admission_decision("jsq", views, 64, prof=prof)[2]
    act, rep, _ = admission_decision("round_robin", views, 64, rr=0,
                                     prof=prof, slo_ttft_s=est1 * 1.5)
    assert (act, rep) == ("redirect", 1)
    # impossible SLO: shed (or least-bad admit with shed=False)
    act, _, _ = admission_decision("round_robin", views, 64, rr=0,
                                   prof=prof, slo_ttft_s=1e-12)
    assert act == "shed"
    act, rep, _ = admission_decision("round_robin", views, 64, rr=0,
                                     prof=prof, slo_ttft_s=1e-12, shed=False)
    assert (act, rep) == ("admit", 1)


def test_router_config_validation():
    with pytest.raises(ValueError):
        RouterConfig(replicas=0)
    with pytest.raises(ValueError):
        RouterConfig(policy="warmest")
    with pytest.raises(ValueError):
        RouterConfig(replicas=2, prefill_replicas=2)
    with pytest.raises(ValueError):
        RouterConfig(replicas=2, prefill_replicas=-1)
    with pytest.raises(ValueError):
        RouterConfig(slo_ttft_s=-1.0)
    assert RouterConfig(replicas=3, prefill_replicas=1).disaggregated


def test_router_set_typo_and_chunk_len_fail_loudly():
    from repro.app.config import RunConfig, set_by_path

    cfg = RunConfig.for_workload("serve")
    set_by_path(cfg, "router.policy", "jsq")       # valid
    assert cfg.router.policy == "jsq"
    with pytest.raises(KeyError):
        set_by_path(cfg, "router.polcy", "jsq")    # typo
    with pytest.raises(KeyError):
        set_by_path(cfg, "router.replica_count", "2")
    with pytest.raises(ValueError):
        ServeConfig(block_size=16, chunk_len=12)   # not a block multiple
    with pytest.raises(ValueError):
        ServeConfig(chunk_len=-16)
    assert ServeConfig(block_size=16).resolved_chunk_len == 32


# --------------------------------------------- offline router evaluation ---


@pytest.mark.parametrize("policy", ["round_robin", "least_kv", "jsq"])
def test_router_workload_conserves_requests(policy):
    reqs = bursty_requests(
        60, 30.0, prompt_lens=(16, 32, 64), max_new_range=(4, 16), seed=5
    )
    tasks = router_workload(
        reqs, policy=policy, n_replicas=2, num_slots=3,
        kv_capacity_tokens=512,
    )
    res = Engine().run(tasks)
    summ = router_summary(res, n_replicas=2)
    assert summ["submitted"] == 60
    assert summ["finished"] + summ["shed"] == 60
    assert summ["shed"] == 0          # no SLO configured -> nothing sheds
    assert summ["ttft_p99_s"] > 0
    assert len(summ["replica_tokens"]) == 2
    assert sum(summ["replica_tokens"]) > 0


def test_router_workload_validates_inputs():
    reqs = poisson_requests(8, 10.0, seed=0)
    with pytest.raises(ValueError):
        router_workload(reqs, n_replicas=0)
    with pytest.raises(ValueError):
        router_workload(reqs, policy="warmest", n_replicas=2)
    with pytest.raises(ValueError):
        router_workload(reqs, n_replicas=2, replica_speeds=(1.0,))


def test_router_workload_slo_sheds_offline():
    reqs = bursty_requests(40, 50.0, prompt_lens=(64,), seed=1)
    tasks = router_workload(
        reqs, policy="jsq", n_replicas=2, num_slots=2,
        slo_ttft_s=1e-9, kv_capacity_tokens=512,
    )
    summ = router_summary(Engine().run(tasks), n_replicas=2)
    assert summ["shed"] == 40 and summ["finished"] == 0


def test_degraded_replica_rewards_load_aware_placement():
    """The regime MegaRoute targets (the paper's straggler theme): one
    replica at a fraction of fleet speed.  Count-balanced round-robin keeps
    feeding the slow replica; queue-aware jsq diverts and wins on tail TTFT
    — and this offline ranking is what the live bench gate must agree with."""
    reqs = bursty_requests(
        120, 40.0, burst_mult=10.0, burst_frac=0.2, burst_dwell_s=0.3,
        prompt_lens=(16, 32, 256), max_new_range=(4, 48), seed=0,
    )
    p99 = {}
    for policy in ("round_robin", "jsq"):
        tasks = router_workload(
            reqs, policy=policy, n_replicas=2, num_slots=4,
            kv_capacity_tokens=600, replica_speeds=(1.0, 0.35),
        )
        summ = router_summary(Engine().run(tasks), n_replicas=2)
        assert summ["finished"] == 120
        p99[policy] = summ["ttft_p99_s"]
    assert p99["round_robin"] / p99["jsq"] > 1.2, p99


# ----------------------------------------------- scheduler migration units ---


def _sched(num_slots=2, num_blocks=9, block_size=8):
    return Scheduler(ServeConfig(
        num_slots=num_slots, num_blocks=num_blocks, block_size=block_size,
        max_blocks_per_slot=4,
    ))


def test_scheduler_adopt_claims_slot_and_blocks():
    s = _sched()
    req = Request(rid=7, prompt=list(range(10)), max_new=4)
    got = s.adopt(req, pos=10, last_tok=3)
    assert got is not None
    slot, phys = got
    assert s.slots[slot] == 7
    assert len(phys) == 2                      # ceil(10 / 8)
    assert s.pos[slot] == 10 and s.last_tok[slot] == 3
    assert list(s.tables[slot, :2]) == phys
    with pytest.raises(ValueError):
        s.adopt(req, pos=10, last_tok=3)       # duplicate rid


def test_scheduler_adopt_returns_none_when_full():
    s = _sched(num_slots=1)
    assert s.adopt(Request(rid=0, prompt=[1] * 8, max_new=2), 8, 1) is not None
    assert s.adopt(Request(rid=1, prompt=[1] * 8, max_new=2), 8, 1) is None


def test_scheduler_release_request_frees_everything():
    s = _sched()
    s.adopt(Request(rid=5, prompt=[1] * 8, max_new=4), 8, 2)
    held = s.allocator.num_held
    assert held > 0
    s.release_request(5)
    assert s.allocator.num_held == 0
    assert 5 not in s.requests and s.active_slots() == []
    with pytest.raises(ValueError):
        s.release_request(5)


# ------------------------------------------------------ live-engine oracles ---


@pytest.fixture(scope="module")
def qwen_router():
    cfg = get_config("qwen2-0.5b", smoke=True).replace(
        compute_dtype="float32", attn_kv_chunk=4096
    )
    params = get_model(cfg).init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _requests(seed, n, lo=4, hi=20, new_lo=3, new_hi=9, vocab=1000):
    rng = np.random.default_rng(seed)
    return [
        (rng.integers(2, vocab, size=int(rng.integers(lo, hi))).tolist(),
         int(rng.integers(new_lo, new_hi)), i * 0.001)
        for i in range(n)
    ]


def _drain_single(cfg, params, scfg, reqs):
    srv = MegaServe(cfg, params, scfg)
    for p, mn, a in reqs:
        srv.submit(p, mn, arrival=a)
    return srv.drain(), srv


@pytest.mark.parametrize("policy", ["round_robin", "least_kv", "jsq"])
def test_router_matches_single_engine_greedy(qwen_router, policy):
    cfg, params = qwen_router
    scfg = ServeConfig(num_slots=3, block_size=8, num_blocks=40,
                       max_blocks_per_slot=8)
    reqs = _requests(0, 8, vocab=cfg.vocab_size)
    ref, _ = _drain_single(cfg, params, scfg, reqs)

    router = Router(cfg, params, scfg,
                    RouterConfig(replicas=2, policy=policy))
    for p, mn, a in reqs:
        router.submit(p, mn, arrival=a)
    outs = router.drain()
    assert outs == ref
    met = router.metrics()
    assert met["finished"] == len(reqs) and met["shed"] == 0
    assert sum(met["placed_per_replica"]) == len(reqs)
    # both replicas actually served (the whole point of a router)
    assert all(n > 0 for n in met["placed_per_replica"])
    assert met["queue_wait_p99_s"] >= 0


def test_router_disaggregated_matches_colocated(qwen_router):
    cfg, params = qwen_router
    scfg = ServeConfig(num_slots=3, block_size=8, num_blocks=40,
                       max_blocks_per_slot=8)
    reqs = _requests(1, 8, vocab=cfg.vocab_size)
    ref, _ = _drain_single(cfg, params, scfg, reqs)

    router = Router(cfg, params, scfg,
                    RouterConfig(replicas=2, prefill_replicas=1))
    for p, mn, a in reqs:
        router.submit(p, mn, arrival=a)
    outs = router.drain()
    assert outs == ref
    met = router.metrics()
    # every multi-token request crossed the prefill -> decode boundary
    assert met["migrations"] > 0
    names = {e.name for e in router.trace_events()}
    assert {"kv_export", "kv_import", "migrate", "route"} <= names
    # decode happened only on the decode tier
    prefill_reqs = router.replicas[0].sched.requests
    assert not prefill_reqs or all(
        len(r.generated) <= 1 for r in prefill_reqs.values()
    )


def test_router_slo_sheds_live(qwen_router):
    cfg, params = qwen_router
    scfg = ServeConfig(num_slots=2, block_size=8, num_blocks=20,
                       max_blocks_per_slot=8)
    reqs = _requests(2, 5, vocab=cfg.vocab_size)
    router = Router(cfg, params, scfg,
                    RouterConfig(replicas=2, policy="jsq", slo_ttft_s=1e-12))
    for p, mn, a in reqs:
        router.submit(p, mn, arrival=a)
    outs = router.drain()
    met = router.metrics()
    assert outs == {} and met["shed"] == len(reqs)
    assert met["shed_rate"] == 1.0
    assert set(router.shed_rids) == set(range(len(reqs)))


def test_kv_export_import_roundtrip_bit_identical(qwen_router):
    cfg, _ = qwen_router
    spec = PoolSpec(num_slots=2, num_blocks=9, block_size=8, max_blocks=4)
    kv = PagedKVCache(cfg, spec)
    key = iter(jax.random.split(jax.random.PRNGKey(3), 256))
    pool = jax.tree.map(
        lambda p: jax.random.normal(next(key), p.shape).astype(p.dtype),
        kv.pool,
    )
    import jax.numpy as jnp

    phys = jnp.asarray([3, 5, 0, 0], jnp.int32)
    bundle = kv.export_slot(pool, phys, jnp.int32(1))
    # the pool stores heads flattened ([n, blocks, bs, K*dh]); a bundle
    # carries the slot's blocks in the dense cache's [.., K, dh] axes
    for b, p, d in zip(jax.tree.leaves(bundle), jax.tree.leaves(pool),
                       jax.tree.leaves(kv.dense)):
        assert p.shape == (d.shape[0], 9, 8, d.shape[3] * d.shape[4])
        assert b.shape == (d.shape[0], 4, 8, *d.shape[3:])
        np.testing.assert_array_equal(
            np.asarray(b), np.asarray(p)[:, np.asarray(phys)].reshape(b.shape))
    # import into a different slot/blocks of a different pool
    pool2 = jax.tree.map(
        lambda p: jax.random.normal(next(key), p.shape).astype(p.dtype),
        kv.pool,
    )
    phys2 = jnp.asarray([7, 2, 0, 0], jnp.int32)
    pool2 = kv.import_slot(pool2, bundle, phys2, jnp.int32(0))
    back = kv.export_slot(pool2, phys2, jnp.int32(0))
    for a, b in zip(jax.tree.leaves(bundle), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunked_prefill_matches_unchunked(qwen_router):
    cfg, params = qwen_router
    from dataclasses import replace as dreplace

    scfg = ServeConfig(num_slots=3, block_size=8, num_blocks=40,
                       max_blocks_per_slot=8)
    reqs = _requests(3, 5, lo=30, hi=60, vocab=cfg.vocab_size)
    ref, _ = _drain_single(cfg, params, scfg, reqs)
    out, srv = _drain_single(
        cfg, params, dreplace(scfg, chunked_prefill=True), reqs)
    assert out == ref
    # long prompts really took the chunked path
    assert any(e.name == "prefill_chunk" for e in srv.trace_events())


def test_chunked_prefill_survives_preemption(qwen_router):
    cfg, params = qwen_router
    from dataclasses import replace as dreplace

    # tight pool: decode growth forces preemption-by-recompute mid-workload
    scfg = ServeConfig(num_slots=3, block_size=8, num_blocks=13,
                       max_blocks_per_slot=8)
    reqs = _requests(2, 6, lo=20, hi=40, new_lo=12, new_hi=24,
                     vocab=cfg.vocab_size)
    ref, a = _drain_single(cfg, params, scfg, reqs)
    out, b = _drain_single(
        cfg, params, dreplace(scfg, chunked_prefill=True), reqs)
    assert out == ref
    assert a.metrics()["preemptions"] > 0   # the oracle actually preempted


def test_queue_wait_split_from_ttft(qwen_router):
    cfg, params = qwen_router
    # 1 slot: later arrivals must queue, so waits are nonzero and ordered
    scfg = ServeConfig(num_slots=1, block_size=8, num_blocks=20,
                       max_blocks_per_slot=8)
    reqs = _requests(4, 4, vocab=cfg.vocab_size)
    _, srv = _drain_single(cfg, params, scfg, reqs)
    met = srv.metrics()
    assert "queue_wait_p50_s" in met and "queue_wait_p99_s" in met
    for r in srv.sched.requests.values():
        assert r.queue_wait is not None and r.ttft is not None
        assert r.queue_wait <= r.ttft + 1e-9
    # with one slot the last request queued behind whole earlier streams
    assert met["queue_wait_p99_s"] > 0


def test_make_workload_traffic_selection():
    from repro.serve.server import make_poisson_workload

    cfg = get_config("qwen2-0.5b", smoke=True)
    kw = dict(n=16, rate=50.0, prompt_lens=(16, 32),
              max_new_range=(2, 8), num_slots=2, seed=0)
    specs_p, _, _ = make_poisson_workload(cfg, **kw)
    specs_b, _, _ = make_poisson_workload(cfg, traffic="bursty", **kw)
    assert [s.arrival for s in specs_p] != [s.arrival for s in specs_b]
    with pytest.raises(ValueError):
        make_poisson_workload(cfg, traffic="weekly", **kw)


def test_router_step_thinning_matches_single_engine(qwen_router):
    cfg, params = qwen_router
    scfg = ServeConfig(num_slots=3, block_size=8, num_blocks=40,
                       max_blocks_per_slot=8)
    reqs = _requests(7, 8, vocab=cfg.vocab_size)
    ref, _ = _drain_single(cfg, params, scfg, reqs)

    # replica 1 stepped every 3rd tick: slower, but greedy streams identical
    router = Router(cfg, params, scfg,
                    RouterConfig(replicas=2, policy="least_kv"),
                    replica_step_every=[1, 3])
    for p, mn, a in reqs:
        router.submit(p, mn, arrival=a)
    outs = router.drain()
    assert outs == ref
    met = router.metrics()
    assert met["finished"] == len(reqs) and met["shed"] == 0

    with pytest.raises(ValueError):
        Router(cfg, params, scfg, RouterConfig(replicas=2),
               replica_step_every=[1])
    with pytest.raises(ValueError):
        Router(cfg, params, scfg, RouterConfig(replicas=2),
               replica_step_every=[1, 0])


def test_precompile_walks_width_ladder_and_stays_exact(qwen_router):
    cfg, params = qwen_router
    scfg = ServeConfig(num_slots=3, block_size=8, num_blocks=40,
                       max_blocks_per_slot=8)
    ref, _ = _drain_single(cfg, params, scfg, _requests(11, 5, vocab=cfg.vocab_size))

    srv = MegaServe(cfg, params, scfg)
    # paged path: one decode variant per pow2 table-width bucket up to the
    # cap, plus the prefill prompt-bucket ladder; counts/ms tally per path
    rep = srv.precompile()
    assert rep["decode"]["count"] == 4
    assert rep["prefill"]["count"] == 4
    assert rep["verify"]["count"] == rep["chunk"]["count"] == 0
    assert rep["total"] == 8
    assert rep["decode"]["ms"] > 0 and rep["prefill"]["ms"] > 0
    for p, mn, a in _requests(11, 5, vocab=cfg.vocab_size):
        srv.submit(p, mn, arrival=a)
    assert srv.drain() == ref
