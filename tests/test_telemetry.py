"""Telemetry subsystem: registry/tracer/recorder/profiler + serving wiring.

The two contracts that matter most are test-pinned here:

  * **Zero perturbation.** Attaching a `Telemetry` bundle never touches an
    executor's compile-cache keys, never retraces, and returns bit-identical
    ids/dists vs the detached pipeline (`test_compile_cache_keys_identical_
    with_telemetry`, `test_pipeline_parity_and_window`).
  * **Total request attribution.** Over a scripted fault-injection
    schedule (`build_schedule`) with tracing on, every submitted query lands on the Chrome
    trace timeline exactly once -- served, cache_hit, shed or expired; zero
    unattributed -- and the flight recorder emits a postmortem for every
    injected failover/degrade transition
    (`test_trace_attribution_over_fault_schedule`).
"""
import json
import os

import numpy as np
import pytest

from repro.core import SearchConfig, brute_force_knn
from repro.runtime import (
    MetricsRegistry,
    MutableBangIndex,
    SearchExecutor,
    ServePipeline,
    Telemetry,
    Tracer,
)
from repro.runtime.hostio import HostIOConfig
from repro.runtime.resilience import (
    FOREVER,
    FaultInjector,
    FaultSpec,
    ResilienceConfig,
)
from repro.runtime.telemetry import (
    LATENCY_BUCKETS_S,
    FlightRecorder,
    HopProfiler,
    log_buckets,
    parse_prom,
    validate_chrome_trace,
)
from repro.runtime.telemetry.registry import Histogram

K = 5
CFG = SearchConfig(t=16)


# ================================================================= registry
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("bang_test_total", "help text")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1.0)            # counters are monotone
    assert c.value == 3.5

    g = reg.gauge("bang_test_gauge")
    g.set(4.0)
    g.set_max(2.0)             # high-watermark: lower value is a no-op
    assert g.value == 4.0
    g.set_max(9.0)
    assert g.value == 9.0
    g.inc(1.0)
    assert g.value == 10.0

    h = reg.histogram("bang_test_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 100.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(101.05)
    assert h.percentile(50.0) == 1.0       # bucket upper bound
    assert h.percentile(100.0) == 10.0     # +Inf overflow clamps to top bound
    assert Histogram("x", "", __import__("threading").Lock(),
                     (1.0,)).percentile(50.0) == 0.0  # empty -> 0.0

    # get-or-create: same handle by name, type conflicts are errors.
    assert reg.counter("bang_test_total") is c
    with pytest.raises(TypeError):
        reg.gauge("bang_test_total")
    with pytest.raises(ValueError):
        reg.counter("0bad name")
    assert "bang_test_total" in reg and len(reg) == 3


def test_log_buckets_and_default_latency_buckets():
    b = log_buckets(1e-5, 10.0, 4)
    assert b == LATENCY_BUCKETS_S
    assert len(b) == 25 and list(b) == sorted(b)
    assert b[0] == pytest.approx(1e-5) and b[-1] == pytest.approx(10.0)
    with pytest.raises(ValueError):
        log_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        Histogram("x", "", __import__("threading").Lock(), (2.0, 1.0))


def test_registry_delta_windows():
    reg = MetricsRegistry()
    reg.counter("c_total").inc(5)
    reg.gauge("g").set(7)
    reg.histogram("h", buckets=(1.0,)).observe(0.5)
    snap = reg.snapshot()
    reg.counter("c_total").inc(3)
    reg.gauge("g").set(2)
    reg.histogram("h").observe(0.5)
    reg.counter("new_total").inc(1)        # born inside the window
    d = reg.delta(snap)
    assert d["c_total"]["value"] == 3
    assert d["g"]["value"] == 2            # gauges pass through current
    assert d["h"]["count"] == 1 and d["h"]["sum"] == pytest.approx(0.5)
    assert d["h"]["buckets"]["1.0"] == 1
    assert d["new_total"]["value"] == 1    # absent from prev -> full value


def test_to_json_and_prom_round_trip():
    reg = MetricsRegistry()
    reg.counter("bang_q_total", "queries").inc(7)
    reg.gauge("bang_qps", "last window").set(123.5)
    h = reg.histogram("bang_lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)

    j = reg.to_json()
    assert j == json.loads(json.dumps(j))  # JSON-serialisable
    assert j["schema_version"] == 1
    assert j["metrics"]["bang_q_total"] == {
        "type": "counter", "value": 7.0, "help": "queries"}

    text = reg.to_prom()
    assert "# TYPE bang_q_total counter" in text
    assert "# HELP bang_lat_seconds latency" in text
    samples = parse_prom(text)             # the CI gate: strict line format
    assert samples["bang_q_total"] == 7
    assert samples["bang_qps"] == 123.5
    # histogram exposition is cumulative per le, plus _sum/_count
    assert samples['bang_lat_seconds_bucket{le="0.1"}'] == 1
    assert samples['bang_lat_seconds_bucket{le="1.0"}'] == 1
    assert samples['bang_lat_seconds_bucket{le="+Inf"}'] == 2
    assert samples["bang_lat_seconds_count"] == 2
    assert samples["bang_lat_seconds_sum"] == pytest.approx(5.05)

    with pytest.raises(ValueError):
        parse_prom("this is not exposition format\n")
    with pytest.raises(ValueError):
        parse_prom("0badname 17\n")


# ================================================================== tracer
def test_tracer_spans_instants_and_chrome_schema(tmp_path):
    tr = Tracer()
    with tr.span("request", track="serve", rid=0):
        pass
    sp = tr.span("gather", track="hostio-p0", rows=4)
    sp.end(seq=9)
    sp.end()                               # double end is a no-op
    tr.instant("failover", shard=0)
    tr.complete("device", 10.0, 20.0, track="serve", size=8)

    evs = validate_chrome_trace(tr.to_chrome())
    names = [e["name"] for e in evs]
    assert names.count("thread_name") == 3   # serve, hostio-p0, events
    gather = next(e for e in evs if e["name"] == "gather")
    assert gather["ph"] == "X" and gather["args"] == {"rows": 4, "seq": 9}
    inst = next(e for e in evs if e["name"] == "failover")
    assert inst["ph"] == "i" and inst["args"] == {"shard": 0}
    # distinct tracks get distinct tids; same track shares one
    serve_tid = next(e for e in evs if e["name"] == "request")["tid"]
    assert next(e for e in evs if e["name"] == "device")["tid"] == serve_tid
    assert gather["tid"] != serve_tid

    p = tmp_path / "trace.json"
    tr.save(str(p))
    with open(p) as f:
        loaded = json.load(f)
    assert validate_chrome_trace(loaded) == evs

    # at_us: absolute perf_counter stamps land on the tracer's clock
    import time
    t0 = time.perf_counter()
    assert tr.at_us(t0) == pytest.approx(tr.now_us(), abs=5e3)


def test_tracer_bounded_and_drop_accounting():
    tr = Tracer(max_events=5)
    for i in range(10):
        tr.instant("tick", track="t", i=i)
    evs = tr.events()
    # 1 thread_name metadata (cap-exempt) + 4 stored instants
    assert len(evs) == 5 and evs[0]["ph"] == "M"
    assert tr.dropped_events == 6
    assert tr.to_chrome()["otherData"]["dropped_events"] == 6


def test_validate_chrome_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": "nope"})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "Z", "name": "x",
                                                "pid": 1, "tid": 0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"ph": "X", "name": "x", "pid": 1, "tid": 0, "ts": 0.0,
             "dur": -1.0}]})


# ========================================================== flight recorder
def test_flightrecorder_ring_and_postmortems(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c_total").inc(4)
    rec = FlightRecorder(capacity=3, registry=reg, max_dumps=1)
    for i in range(5):
        rec.record("tick", i=i)
    assert [e["i"] for e in rec.events()] == [2, 3, 4]  # oldest evicted

    dump = rec.trigger("failover", shard=0)
    assert dump["schema_version"] == 1 and dump["seq"] == 0
    assert dump["reason"] == "failover" and dump["context"] == {"shard": 0}
    # the trigger itself is the ring's newest entry at dump time
    assert dump["events"][-1]["kind"] == "trigger:failover"
    assert dump["metrics"]["c_total"]["value"] == 4
    assert rec.dumps_for("failover") == [dump]

    rec.trigger("degraded", shard=0)       # over max_dumps -> counted, not kept
    assert len(rec.dumps) == 1 and rec.dropped_dumps == 1

    p = tmp_path / "postmortems.json"
    rec.save(str(p))
    with open(p) as f:
        doc = json.load(f)
    assert doc["schema_version"] == 1 and doc["dropped_dumps"] == 1
    assert [d["reason"] for d in doc["dumps"]] == ["failover"]

    rec.clear()
    assert rec.events() == [] and rec.dumps == [] and rec.dropped_dumps == 0


# ================================================================ profiler
def test_hop_profiler_summary_and_bounds():
    prof = HopProfiler(max_hops=3)
    prof.on_hop(0, lanes=8, own_lanes=4, cache_hit_lanes=2, wall_s=0.002)
    prof.on_hop(0, lanes=8, own_lanes=8, cache_hit_lanes=0, wall_s=0.001)
    prof.on_hop(0, lanes=8, own_lanes=2, cache_hit_lanes=0, wall_s=0.004)
    prof.on_hop(0, lanes=8, own_lanes=1, cache_hit_lanes=0, wall_s=0.1)
    assert prof.hops == 3 and prof.dropped_hops == 1  # bounded

    s = prof.summary()
    assert s["hops"] == 3
    assert s["hop_wall_s_total"] == pytest.approx(0.007)
    assert s["hop_wall_s_max"] == pytest.approx(0.004)
    assert s["frontier_occupancy"] == pytest.approx((4 + 2 + 8 + 2) / 24)
    assert s["own_lanes_total"] == 14 and s["cache_hit_lanes_total"] == 2
    # no dispatch stamped kernel info -> no codes-stream model
    assert s["kernel_info"] is None
    assert s["codes_stream_bytes_per_hop"] is None

    prof.set_kernel_info(kernel_mode="reference", batch=8, n=1000, m=8, R=16)
    s = prof.summary()
    assert s["kernel_info"]["kernel_mode"] == "reference"
    per_hop = s["codes_stream_bytes_per_hop"]
    assert per_hop is not None and per_hop >= 0
    assert s["codes_stream_bytes_total"] == per_hop * s["hops"]


# ========================================================= telemetry bundle
def test_telemetry_create_flags():
    tel = Telemetry.create()
    assert tel.registry is not None
    assert tel.tracer is None and tel.recorder is None and tel.profiler is None
    # disabled shortcuts are harmless no-ops
    assert tel.span("x") is None
    tel.instant("x")
    tel.record("x")
    tel.event("x")

    full = Telemetry.create(trace=True, flight_record=True, profile=True,
                            max_dumps=7)
    assert full.tracer is not None and full.profiler is not None
    assert full.recorder is not None
    assert full.recorder._registry is full.registry  # snapshot-in-dump wiring
    assert full.recorder._max_dumps == 7

    reg = MetricsRegistry()
    assert Telemetry.create(registry=reg).registry is reg
    from repro.runtime.telemetry import default_registry
    assert Telemetry.create(shared_registry=True).registry \
        is default_registry()


def test_bump_hostio_counter_mapping():
    tel = Telemetry.create()
    reg = tel.registry
    tel.bump_hostio({"requests": 2, "degraded_lanes": 3,
                     "max_queue_depth": 7, "gather_s_total": 0.5,
                     "gather_s_hidden": 0.25, "latency_s_total": 0.75})
    assert reg.counter("bang_hostio_requests_total").value == 2
    assert reg.counter("bang_hostio_degraded_lanes_total").value == 3
    assert reg.counter("bang_hostio_gather_seconds_total").value == 0.5
    assert reg.counter(
        "bang_hostio_gather_hidden_seconds_total").value == 0.25
    assert reg.counter(
        "bang_hostio_request_latency_seconds_total").value == 0.75
    # max_queue_depth is a high-watermark gauge, not a counter
    tel.bump_hostio({"max_queue_depth": 3})
    assert reg.gauge("bang_hostio_max_queue_depth").value == 7
    tel.bump_hostio({"requests": 1})
    assert reg.counter("bang_hostio_requests_total").value == 3


# ===================================================== executor: zero cost
def test_compile_cache_keys_identical_with_telemetry(small_ann_index):
    """Telemetry must never enter the compile-cache key or force a retrace."""
    data, idx = small_ann_index
    q = np.asarray(data[:4] + 0.01, np.float32)
    ex_off = SearchExecutor.from_index(idx, variant="inmem")
    ex_on = SearchExecutor.from_index(idx, variant="inmem")
    tel = Telemetry.create(trace=True, flight_record=True, profile=True)
    assert ex_on.set_telemetry(tel) is ex_on

    ids_off, d_off = ex_off.search(q, K, cfg=CFG)
    ids_on, d_on = ex_on.search(q, K, cfg=CFG)
    np.testing.assert_array_equal(np.asarray(ids_on), np.asarray(ids_off))
    np.testing.assert_array_equal(np.asarray(d_on), np.asarray(d_off))

    # byte-identical keys: same tuples, same order, same repr
    assert list(ex_on._cache.keys()) == list(ex_off._cache.keys())
    assert repr(sorted(map(repr, ex_on._cache))) == \
        repr(sorted(map(repr, ex_off._cache)))

    # attach/detach cycles never compile or retrace anything new
    before = (ex_on.cache_size, ex_on.n_traces)
    ex_on.set_telemetry(None)
    ex_on.search(q, K, cfg=CFG)
    ex_on.set_telemetry(tel)
    ex_on.search(q, K, cfg=CFG)
    assert (ex_on.cache_size, ex_on.n_traces) == before

    # the one compile that did happen was accounted while attached
    assert tel.registry.counter("bang_serve_compile_seconds_total").value > 0
    compiles = [e for e in tel.tracer.events() if e["name"] == "compile"]
    assert len(compiles) == 1 and compiles[0]["args"]["k"] == K
    # profiler saw the dispatch-time kernel stamp
    assert tel.profiler.summary()["kernel_info"]["kernel_mode"] \
        == CFG.kernel_mode


@pytest.mark.parametrize("variant", ["inmem", "base"])
def test_compile_span_and_gauge_carry_executable_memory(small_ann_index,
                                                        variant):
    """The compile span gives the new executable's device temporaries and
    operands (`memory_analysis()`), and the gauge holds the temporaries."""
    data, idx = small_ann_index
    tel = Telemetry.create(trace=True)
    ex = SearchExecutor.from_index(idx, variant=variant).set_telemetry(tel)
    ex.search(np.asarray(data[:4] + 0.01, np.float32), K, cfg=CFG)
    (span,) = [e for e in tel.tracer.events() if e["name"] == "compile"]
    assert span["args"]["temp_bytes"] > 0
    assert span["args"]["argument_bytes"] > 0
    assert tel.registry.gauge("bang_serve_executable_temp_bytes").value \
        == span["args"]["temp_bytes"]


# ==================================================== pipeline: parity + window
def test_pipeline_parity_and_window(small_ann_index):
    """Full-bundle serving is bit-exact vs detached, and the window adds up."""
    data, idx = small_ann_index
    rng = np.random.default_rng(11)
    q = np.asarray(data[rng.integers(len(data), size=16)] + 0.05, np.float32)
    gt = np.asarray(brute_force_knn(data, q, K))
    hio = HostIOConfig(workers=2, hot_cache_rows=64, prefetch=True)

    def _run(telemetry):
        ex = SearchExecutor.from_index(idx, variant="base", hostio=hio)
        with ServePipeline(ex, k=K, cfg=CFG, max_batch=8,
                           telemetry=telemetry) as pipe:
            pipe.submit(q, gt_ids=gt)
            return pipe.drain()

    ids_off, d_off, st_off = _run(None)
    assert st_off.telemetry is None

    tel = Telemetry.create(trace=True, flight_record=True, profile=True)
    ids_on, d_on, st_on = _run(tel)
    np.testing.assert_array_equal(np.asarray(ids_on), np.asarray(ids_off))
    np.testing.assert_array_equal(np.asarray(d_on), np.asarray(d_off))

    # ServeStats.telemetry is the registry delta over the drain window
    w = st_on.telemetry
    assert w["bang_serve_queries_total"]["value"] == 16
    assert w["bang_serve_batches_total"]["value"] == st_on.batches == 2
    assert w["bang_serve_latency_seconds"]["count"] == 16
    assert w["bang_serve_qps"]["value"] == pytest.approx(st_on.qps)
    assert w["bang_serve_recall"]["value"] == \
        pytest.approx(st_on.mean_recall)
    # hostio counters mirror into the registry 1:1 with the service window
    assert w["bang_hostio_requests_total"]["value"] == \
        st_on.hostio["requests"]
    assert tel.registry.gauge("bang_hostio_hot_cache_rows").value == 64
    assert tel.registry.gauge(
        "bang_hostio_hot_cache_device_bytes").value > 0

    # trace: schema-valid, every rid served exactly once, hostio track live
    evs = validate_chrome_trace(tel.tracer.to_chrome())
    served = sorted(e["args"]["rid"] for e in evs if e["name"] == "request")
    assert served == list(range(16))
    tracks = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert {"serve", "hostio-p0"} <= tracks
    gathers = [e for e in evs if e["name"] == "gather"]
    assert gathers and all(e["args"]["mode"] == "collect" for e in gathers)
    assert any(e["name"] == "prefetch_gather" for e in evs)

    # profiler rode the host-callback seam
    s = tel.profiler.summary()
    assert s["hops"] == len(gathers)
    assert 0.0 < s["frontier_occupancy"] <= 1.0
    assert s["cache_hit_lanes_total"] > 0      # 64 hot rows + medoid pin

    # and the whole registry exports as valid exposition format
    samples = parse_prom(tel.registry.to_prom())
    assert samples["bang_serve_queries_total"] == 16


# ============================================ acceptance: fault schedule
def build_schedule(svc, *, seed: int = 7) -> list:
    """The scripted fault schedule: [(phase, setup, teardown), ...].

    The same query batch replays through every phase so bit-exactness vs
    the healthy phase is checkable; `svc` is the executor's
    `NeighborService`. Health transitions are scripted, never inferred: the
    caller's `ResilienceConfig` sets `unhealthy_after` effectively infinite
    and `auto_failover` off, so every phase boundary is an explicit
    `mark_partition_down` / `fail_over` / `recover` call.
    """
    def _inject(*specs):
        svc.set_injector(FaultInjector(specs, seed=seed))

    return [
        ("healthy", lambda: None, lambda: None),
        # count=2, not FOREVER: the retry budget (max_retries=3) must be
        # able to absorb every injected failure or lanes would degrade and
        # break the phase's bit-exactness.
        ("transient",
         lambda: _inject(FaultSpec("transient_error", shard=0, count=2)),
         lambda: svc.set_injector(None)),
        # Stall (0.15 s) > hedge budget (0.05 s): every stalled pooled
        # gather / ticket is abandoned and re-gathered inline, bit-exact.
        ("stalled",
         lambda: _inject(FaultSpec("worker_stall", stall_s=0.15,
                                   count=FOREVER)),
         lambda: svc.set_injector(None)),
        ("degraded",
         lambda: svc.mark_partition_down(0), lambda: None),
        ("failover",
         lambda: svc.fail_over(0), lambda: None),
        ("recovered",
         lambda: svc.recover(0), lambda: None),
    ]


def test_trace_attribution_over_fault_schedule(small_ann_index):
    """Drive `build_schedule` with tracing + flight recording on.

    Acceptance contract: every submitted query is attributed on the trace
    timeline exactly once (served / cache_hit / shed / expired -- zero
    unattributed), and the flight recorder emits a postmortem per injected
    failover/degrade transition.
    """
    data, idx = small_ann_index
    q = np.asarray(data[:12] + 0.02, np.float32)
    gt = np.asarray(brute_force_knn(data, q, K))
    hio = HostIOConfig(
        # Small cache: most lanes MISS, so a downed partition actually
        # degrades lanes (full coverage would hide the degrade path).
        workers=2, hot_cache_rows=64, prefetch=True,
        resilience=ResilienceConfig(
            deadline_s=0.25, hedge_s=0.05, max_retries=3,
            unhealthy_after=1_000_000, auto_failover=False,
            degraded_mode="medoid",
        ),
    )
    ex = SearchExecutor.from_index(idx, variant="base", hostio=hio)
    svc = ex.hostio_service
    tel = Telemetry.create(trace=True, flight_record=True,
                           ring_capacity=4096, max_dumps=4096)
    rec = tel.recorder
    pipe = ServePipeline(ex, k=K, cfg=CFG, max_batch=12, max_queue=24,
                         telemetry=tel)
    try:
        results = {}
        for phase, setup, teardown in build_schedule(svc):
            setup()
            assert pipe.submit(q, gt_ids=gt) == 12
            ids, dists, stats = pipe.drain()
            teardown()
            results[phase] = (np.asarray(ids).copy(),
                              np.asarray(dists).copy(), stats)

        # retry/hedge/failover phases are bit-exact vs healthy; only the
        # degraded phase may differ (medoid-restart serving)
        ids_h, d_h, _ = results["healthy"]
        for phase in ("transient", "stalled", "failover", "recovered"):
            np.testing.assert_array_equal(results[phase][0], ids_h, phase)
            np.testing.assert_array_equal(results[phase][1], d_h, phase)
        assert results["degraded"][2].telemetry[
            "bang_hostio_degraded_lanes_total"]["value"] > 0

        # tail window: expired rows (deadline already passed at drain) and
        # shed rows (burst past the 24-row admission bound), same drain
        assert pipe.submit(q, deadline_s=1e-6) == 12
        assert pipe.submit(q) == 12
        assert pipe.submit(q) == 0          # queue full -> all 12 shed
        _, _, tail = pipe.drain()
        assert tail.expired_queries == 12 and tail.shed_queries == 12
    finally:
        pipe.close()

    # ---- total attribution: one terminal event per submitted rid --------
    evs = validate_chrome_trace(tel.tracer.to_chrome())
    assert tel.tracer.dropped_events == 0
    terminal: list[int] = []
    outcomes = {"request": 0, "request_shed": 0, "request_expired": 0}
    for e in evs:
        if e["name"] in outcomes:
            outcomes[e["name"]] += 1
            terminal.append(e["args"]["rid"])
    n_submitted = pipe._next_rid
    assert n_submitted == 12 * 9            # 6 phases + 3 tail submits
    assert sorted(terminal) == list(range(n_submitted))  # zero unattributed
    assert outcomes == {"request": 12 * 7, "request_shed": 12,
                        "request_expired": 12}

    # ---- postmortems: one per injected failover/degrade transition ------
    assert len(rec.dumps_for("partition_down")) == 1   # mark_partition_down
    assert len(rec.dumps_for("failover")) == 1         # fail_over(0)
    assert len(rec.dumps_for("degraded")) >= 1         # degraded-lane gathers
    assert rec.dropped_dumps == 0
    pm = rec.dumps_for("failover")[0]
    assert pm["context"]["shard"] == 0
    assert pm["metrics"]["bang_serve_queries_total"]["value"] > 0
    # injected faults left ring entries a postmortem can explain itself with
    kinds = {e["kind"] for e in rec.events()}
    assert "fault_injected" in kinds
    # recovery is an event (timeline instant), deliberately not a postmortem
    assert any(e["name"] == "recover" for e in evs)
    assert rec.dumps_for("recover") == []


# ================================================================ mutation
def test_mutation_telemetry(small_ann_index):
    data, idx = small_ann_index
    tel = Telemetry.create(trace=True)
    reg = tel.registry
    with MutableBangIndex(idx) as mut:
        mut.set_telemetry(tel)
        gids = mut.insert(np.asarray(data[:3] + 0.25, np.float32))
        mut.delete([int(gids[0])])
        assert reg.counter("bang_mutation_inserts_total").value == 3
        assert reg.counter("bang_mutation_deletes_total").value == 1
        ex = mut.executor("inmem")
        assert reg.gauge("bang_mutation_epoch").value == ex.mutation_epoch

        mut.consolidate()
        assert reg.counter("bang_mutation_consolidations_total").value == 1
        assert reg.gauge("bang_mutation_generation").value == mut.generation

        evs = tel.tracer.events()
        cons = [e for e in evs if e["name"] == "consolidate"]
        assert len(cons) == 1 and cons[0]["ph"] == "X"
        assert cons[0]["args"]["to_generation"] == mut.generation
        swap = [e for e in evs if e["name"] == "generation_swap"]
        assert len(swap) == 1
        assert swap[0]["args"]["generation"] == mut.generation

        # the bundle survives the generation swap: the post-consolidation
        # inner executor still accounts its compiles through the registry
        before = reg.counter("bang_serve_compile_seconds_total").value
        ids, _ = ex.search(np.asarray(data[:2], np.float32), K, cfg=CFG)
        assert np.asarray(ids).shape == (2, K)
        assert reg.counter(
            "bang_serve_compile_seconds_total").value > before


# ============================================== stage scopes and host spans
BASE_HIO = HostIOConfig()          # the Base deployment's host-I/O service


@pytest.mark.parametrize("mode", ["reference", "staged"])
@pytest.mark.parametrize("prefetch", [False, True])
def test_stage_map_holds_every_stage(small_ann_index, mode, prefetch):
    """Every stage scope reaches the compiled Base program's op metadata; the
    host callbacks belong to the exchange or the re-rank, the prefetch ones
    too though they are issued from inside the step."""
    from repro.core.search import STAGES

    data, idx = small_ann_index
    ex = SearchExecutor.from_index(
        idx, variant="base", hostio=HostIOConfig(prefetch=prefetch))
    assert ex.stage_map() == {}                  # nothing compiled yet
    ex.search(np.asarray(data[:4]), K, cfg=SearchConfig(t=16,
                                                        kernel_mode=mode))
    stages = ex.stage_map()
    assert set(stages.values()) == set(STAGES)
    callbacks = {stages.get(n) for n in stages if n.startswith("pure_callback")}
    assert callbacks == {"bang.fetch", "bang.rerank"}


def test_op_stages_from_metadata_fused_computations_and_neighbours():
    """An op's own innermost scope; else its fused computation's; else the
    one stage of its users or operands; else its caller's. The search's own
    loop keeps none."""
    from repro.core.search import STAGES
    from repro.runtime.telemetry.stages import op_stages

    text = """HloModule jit_pipeline
%fused.1 (p.1: f32[2]) -> f32[2] {
  %p.1 = f32[2]{0:T(128)} parameter(0)
  ROOT %m.1 = f32[2]{0:T(128)} multiply(%p.1, %p.1), metadata={op_name="jit(p)/while/body/bang.bloom/mul"}
}
%relayout.body (q.1: f32[2]) -> f32[2] {
  %q.1 = f32[2]{0:T(128)} parameter(0)
  ROOT %r.1 = f32[2]{0:T(128)} negate(%q.1)
}
%loop.body (q.2: f32[2]) -> f32[2] {
  %q.2 = f32[2]{0:T(128)} parameter(0)
  ROOT %l.1 = f32[2]{0:T(128)} negate(%q.2)
}
ENTRY %main (x: f32[2]) -> f32[2] {
  %x = f32[2]{0:T(128)} parameter(0)
  %a.1 = f32[2]{0:T(1024)(128)} add(%x, %x), metadata={op_name="jit(p)/bang.table/add"}
  %b.2 = f32[2]{0} custom-call(%a.1), metadata={op_name="jit(p)/while/body/bang.step/bang.fetch/pure_callback"}
  %c.3 = f32[2]{0:T(1024)(128)} copy(%b.2)
  %w.4 = f32[2]{0} while(%c.3), condition=%relayout.body, body=%relayout.body
  %f.5 = f32[2]{0} fusion(%w.4), kind=kCustom, calls=%fused.1
  %mix = (f32[2]{0}, f32[2]{0}) tuple(%a.1, %b.2)
  ROOT %s.6 = (f32[2]{0}, f32[2]{0}) while(%mix), body=%loop.body, metadata={op_name="jit(p)/while"}
}
"""
    got = op_stages(text, STAGES)
    assert got["a.1"] == "bang.table"
    assert got["b.2"] == "bang.fetch"         # innermost of step/fetch
    assert got["f.5"] == "bang.bloom"         # its fused computation's
    assert got["w.4"] == "bang.bloom"         # its one user's
    assert got["c.3"] == "bang.bloom"         # its one user's, through w.4
    assert got["r.1"] == "bang.bloom"         # its loop's, w.4
    assert got["s.6"] is None                 # the search loop: mixed input
    assert got["l.1"] is None                 # and its body keeps none


def test_stage_map_leaves_out_names_the_executables_disagree_on():
    text = """HloModule jit_pipeline
ENTRY %main (x: f32[2]) -> f32[2] {
  %a.1 = f32[2]{0} add(%x, %x), metadata={op_name="jit(p)/bang.table/add"}
  ROOT %b.2 = f32[2]{0} custom-call(%a.1), metadata={op_name="jit(p)/bang.fetch/pure_callback"}
}
"""

    class Fake:
        def __init__(self, text):
            self.text = text

        def as_text(self):
            return self.text

    ex = SearchExecutor.__new__(SearchExecutor)
    ex._cache = {1: Fake(text), 2: Fake(text.replace("bang.table", "x"))}
    # a.1 is bang.table in one executable and bang.fetch's input in the other
    assert ex.stage_map() == {"b.2": "bang.fetch"}


def _host_plane_events(trace_dir) -> list[tuple[str, int, int]]:
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out.extend((e.name, int(e.start_ns), int(e.end_ns))
                           for e in line.events if e.name.startswith("bang."))
    return out


def test_spans_land_on_the_profiler_host_plane(small_ann_index, tmp_path):
    """While a profiler trace runs, each span that wraps work is also a
    `bang.<name>` event on its host plane, on the device's clock, agreeing
    with the Chrome-JSON span; the re-rank records one span per callback."""
    import jax

    from repro.core.rerank import gather_chunk_rows

    data, idx = small_ann_index
    cfg = SearchConfig(t=16)
    bucket = 64
    q = np.asarray(data[:2 * bucket] + 0.01, np.float32)
    tel = Telemetry.create(trace=True)
    ex = SearchExecutor.from_index(idx, variant="base", hostio=BASE_HIO)
    with ServePipeline(ex, k=K, cfg=cfg, max_batch=bucket,
                       telemetry=tel) as pipe:
        pipe.submit(q[:bucket])
        pipe.drain()                        # compile outside the trace
        t_trace = tel.tracer.now_us()
        jax.profiler.start_trace(str(tmp_path))
        try:
            pipe.submit(q)
            pipe.drain()
        finally:
            jax.profiler.stop_trace()

    spans = {}
    for e in tel.tracer.events():
        if e["ph"] == "X" and e["ts"] >= t_trace:
            spans.setdefault(e["name"], []).append(e)
    host = {}
    for n, s, e in _host_plane_events(tmp_path):
        host.setdefault(n[len("bang."):], []).append((s, e))
    names = ("gather", "dispatch", "drain", "rerank_gather")
    assert set(names) <= set(host)
    (drain,) = spans["drain"]
    (drain_host,) = host["drain"]
    for name in names:
        ours = sorted(spans[name], key=lambda e: e["ts"])
        theirs = sorted(host[name])
        assert len(ours) == len(theirs), name
        for e, (s, t) in zip(ours, theirs):
            # durations, and starts from the drain's, within 0.1 ms
            assert e["dur"] == pytest.approx((t - s) / 1e3, abs=100), name
            assert e["ts"] - drain["ts"] == pytest.approx(
                (s - drain_host[0]) / 1e3, abs=100), name

    # one rerank_gather span per callback: the chunks of (bucket, C) ids
    rows = bucket * cfg.iters()
    chunks = -(-rows // gather_chunk_rows(data.shape[1]))
    assert chunks > 1
    assert len(spans["rerank_gather"]) == 2 * chunks
    assert sum(e["args"]["rows"] for e in spans["rerank_gather"]) == 2 * rows


def test_gc_spans_inside_a_traced_drain(small_ann_index):
    import gc

    data, idx = small_ann_index
    tel = Telemetry.create(trace=True)
    ex = SearchExecutor.from_index(idx, variant="inmem")
    n_callbacks = len(gc.callbacks)

    def collect(_report):
        assert len(gc.callbacks) == n_callbacks + 1
        gc.collect()

    with ServePipeline(ex, k=K, cfg=CFG, max_batch=8, telemetry=tel) as pipe:
        pipe.submit(np.asarray(data[:8]))
        pipe.drain(on_batch=collect)
    assert len(gc.callbacks) == n_callbacks
    (drain,) = [e for e in tel.tracer.events() if e["name"] == "drain"]
    gcs = [e for e in tel.tracer.events() if e["name"] == "gc"]
    assert any(e["args"]["generation"] == 2 for e in gcs)
    for e in gcs:
        assert e["args"]["collected"] >= 0
        assert drain["ts"] <= e["ts"] <= e["ts"] + e["dur"] <= \
            drain["ts"] + drain["dur"]


@pytest.mark.parametrize("tel", [None, "registry_only"])
def test_no_tracer_creates_no_span_annotation_or_gc_hook(
        small_ann_index, monkeypatch, tel):
    from repro.runtime.telemetry import tracing

    def forbidden(*a, **kw):
        raise AssertionError("created with no tracer attached")

    monkeypatch.setattr(tracing, "TraceAnnotation", forbidden)
    monkeypatch.setattr(tracing.Span, "__init__", forbidden)
    import gc

    n_callbacks = len(gc.callbacks)
    data, idx = small_ann_index
    telemetry = None if tel is None else Telemetry.create()
    ex = SearchExecutor.from_index(idx, variant="base", hostio=BASE_HIO)
    with ServePipeline(ex, k=K, cfg=CFG, max_batch=8,
                       telemetry=telemetry) as pipe:
        pipe.submit(np.asarray(data[:16]))
        pipe.drain(on_batch=lambda _r: gc.collect())
        assert len(gc.callbacks) == n_callbacks


def test_compiled_base_program_does_not_keep_its_executor(small_ann_index):
    """The re-rank callbacks read the executor's telemetry at call time, yet
    a dropped executor (its host data with it) is still freed."""
    import gc
    import weakref

    data, idx = small_ann_index
    ex = SearchExecutor.from_index(idx, variant="base", hostio=BASE_HIO)
    ex.set_telemetry(Telemetry.create(trace=True))
    ex.search(np.asarray(data[:8]), K, cfg=CFG)
    ref = weakref.ref(ex)
    del ex
    gc.collect()
    assert ref() is None
