"""tpudl.obs.requestlog + tpudl.obs.metering: the durable request log
and the per-tenant metering plane (ISSUE 16).

The contract under test: every terminal Result leaves exactly one
versioned-schema JSONL record in crc-committed rotated segments; the
writer's bounded queue never blocks (overflow is counted, not waited
out); the reader recovers every committed record across rotation and
past a truncated tail (loudly), raises on non-tail corruption, and
checkpoints/restores its position with the ft.data.ResumableIterator
state dict; and the per-tenant rollups the meter renders (and the
report CLI tabulates) reconcile EXACTLY with the live Results.
"""

import json
import os
import threading
import zlib
from collections import Counter

import numpy as np
import pytest

from tpudl.analysis.registry import KNOBS
from tpudl.ft.data import resumable_request_log
from tpudl.obs import counters as obs_counters
from tpudl.obs import metering, requestlog
from tpudl.obs import report as obs_report
from tests.serve_helpers import (
    PROMPT_LEN,
    build_tenant_session,
    make_adapters,
)


@pytest.fixture(autouse=True)
def _clean_requestlog(monkeypatch):
    """Writer + meter + registry are process-global; isolate every
    test (the span-stream _clean_obs idiom, extended)."""
    monkeypatch.delenv("TPUDL_OBS_DIR", raising=False)
    monkeypatch.delenv("TPUDL_OBS_REQUEST_LOG", raising=False)
    requestlog.disable()
    metering.meter().reset()
    obs_counters.registry().reset()
    yield
    requestlog.disable()
    metering.meter().reset()
    obs_counters.registry().reset()


def _rec(i, tenant=None, finish_reason="eos", **kw):
    kw.setdefault("tokens_in", 3)
    kw.setdefault("tokens_out", 5)
    kw.setdefault("ts", float(i))
    return requestlog.build_record(
        f"r{i}", finish_reason, tenant=tenant, **kw
    )


def _ids(records):
    return [r["request_id"] for r in records]


# ---------------------------------------------------------------------------
# writer: rotation, commit-or-invisible, restart
# ---------------------------------------------------------------------------


def test_rotation_roundtrip(tmp_path):
    """N records across a forced rotation boundary come back in order,
    every segment committed with its crc32 in the name."""
    d = str(tmp_path)
    w = requestlog.RequestLogWriter(d, segment_bytes=256)
    for i in range(20):
        w.log(_rec(i))
    w.close()
    assert w.dropped == 0 and w.written == 20

    segs = requestlog.list_segments(d)
    assert len(segs) >= 2, "segment_bytes=256 must force a rotation"
    assert w.segments_committed == len(segs)
    for idx, crc, path in segs:
        assert crc is not None, f"uncommitted segment survived: {path}"
        with open(path, "rb") as f:
            assert (zlib.crc32(f.read()) & 0xFFFFFFFF) == crc
    assert [idx for idx, _, _ in segs] == sorted(
        idx for idx, _, _ in segs
    )

    records = list(requestlog.read_request_log(d))
    assert _ids(records) == [f"r{i}" for i in range(20)]
    assert all(r["v"] == requestlog.SCHEMA_VERSION for r in records)


def test_close_commits_open_tail(tmp_path):
    """close() publishes the partial tail segment: after close there
    is no .open file left and every record is crc-guarded."""
    d = str(tmp_path)
    w = requestlog.RequestLogWriter(d, segment_bytes=1 << 20)
    for i in range(3):
        w.log(_rec(i))
    w.close()
    names = os.listdir(d)
    assert not any(n.endswith(".open.jsonl") for n in names), names
    assert _ids(list(requestlog.read_request_log(d))) == [
        "r0", "r1", "r2"
    ]
    w.close()  # idempotent


def test_restart_never_appends_into_old_segments(tmp_path):
    """A new writer starts past the highest index on disk — a restart
    cannot touch (or recommit) a previous process's segments."""
    d = str(tmp_path)
    w1 = requestlog.RequestLogWriter(d, segment_bytes=1 << 20)
    for i in range(3):
        w1.log(_rec(i))
    w1.close()
    first = {idx for idx, _, _ in requestlog.list_segments(d)}

    w2 = requestlog.RequestLogWriter(d, segment_bytes=1 << 20)
    for i in range(3, 5):
        w2.log(_rec(i))
    w2.close()
    segs = requestlog.list_segments(d)
    assert {idx for idx, _, _ in segs} > first
    assert _ids(list(requestlog.read_request_log(d))) == [
        f"r{i}" for i in range(5)
    ]


def test_overflow_drops_counted_never_blocks(tmp_path):
    """With the writer thread wedged mid-write, a full queue drops (and
    counts) instead of blocking the caller — the decode loop never
    waits on disk."""
    d = str(tmp_path)
    w = requestlog.RequestLogWriter(d, queue_depth=2)
    entered, gate = threading.Event(), threading.Event()
    orig = w._write_one

    def wedged(rec):
        entered.set()
        gate.wait(timeout=30.0)
        orig(rec)

    w._write_one = wedged
    try:
        w.log(_rec(0))
        assert entered.wait(timeout=10.0)  # thread holds r0, blocked
        w.log(_rec(1))
        w.log(_rec(2))  # queue now full (depth 2)
        w.log(_rec(3))  # must return immediately, counted as dropped
        w.log(_rec(4))
        assert w.dropped == 2
        assert (
            obs_counters.registry()
            .counter("requestlog_records_dropped").value == 2
        )
    finally:
        gate.set()
    w.close()
    assert _ids(list(requestlog.read_request_log(d))) == [
        "r0", "r1", "r2"
    ]
    assert w.written == 3


# ---------------------------------------------------------------------------
# reader: tail recovery, non-tail corruption, position resume
# ---------------------------------------------------------------------------


def test_truncated_open_tail_recovered_with_warning(tmp_path):
    """A torn .open tail (crash before commit) yields every intact
    record before the tear, with a loud RuntimeWarning — never silent
    loss, never a crash."""
    d = str(tmp_path)
    w = requestlog.RequestLogWriter(d, segment_bytes=1 << 20)
    for i in range(5):
        w.log(_rec(i))
    w.flush()  # on disk, still .open (uncommitted — crash imminent)
    opens = [n for n in os.listdir(d) if n.endswith(".open.jsonl")]
    assert len(opens) == 1
    path = os.path.join(d, opens[0])
    blob = open(path, "rb").read()
    with open(path, "wb") as f:  # tear mid final record
        f.write(blob[:-7])

    with pytest.warns(RuntimeWarning, match="truncated"):
        records = list(requestlog.read_request_log(d))
    assert _ids(records) == [f"r{i}" for i in range(4)]
    w.close()


def _write_raw_segment(d, idx, records, tail=b"", committed=False):
    blob = b"".join(
        (json.dumps(r) + "\n").encode("utf-8") for r in records
    ) + tail
    if committed:
        crc = zlib.crc32(blob) & 0xFFFFFFFF
        name = f"requests-{idx:06d}-{crc:08x}.jsonl"
    else:
        name = f"requests-{idx:06d}.open.jsonl"
    with open(os.path.join(d, name), "wb") as f:
        f.write(blob)


def test_orphan_open_mid_log_tolerated_by_reader(tmp_path):
    """Tail tolerance follows COMMITMENT, not position: a crashed
    process's torn .open segment stays readable (intact prefix, loud
    warning) even once a restarted writer has published newer segments
    behind it — it must never flip the whole log to
    RequestLogCorruptError."""
    d = str(tmp_path)
    _write_raw_segment(
        d, 0, [_rec(i) for i in range(3)], tail=b'{"torn'
    )
    _write_raw_segment(
        d, 1, [_rec(i) for i in range(3, 6)], committed=True
    )
    with pytest.warns(RuntimeWarning, match="truncated"):
        records = list(requestlog.read_request_log(d))
    assert _ids(records) == [f"r{i}" for i in range(6)]


def test_restart_seals_orphan_open_segment(tmp_path):
    """A new writer crc-seals a predecessor's orphaned .open segment on
    startup — torn final line trimmed loudly, intact records upgraded
    to full crc protection, nothing left uncommitted mid-log."""
    d = str(tmp_path)
    _write_raw_segment(
        d, 0, [_rec(i) for i in range(3)], tail=b'{"torn'
    )
    with pytest.warns(RuntimeWarning, match="torn record"):
        w = requestlog.RequestLogWriter(d, segment_bytes=1 << 20)
    w.log(_rec(3))
    w.close()
    assert not any(
        n.endswith(".open.jsonl") for n in os.listdir(d)
    )
    segs = requestlog.list_segments(d)
    assert [idx for idx, _, _ in segs] == [0, 1]
    for _, crc, path in segs:
        assert crc is not None
        with open(path, "rb") as f:
            assert (zlib.crc32(f.read()) & 0xFFFFFFFF) == crc
    assert (
        obs_counters.registry()
        .counter("requestlog_orphans_sealed").value == 1
    )
    # Fully committed now: reading warns about nothing.
    import warnings as _warnings

    with _warnings.catch_warnings():
        _warnings.simplefilter("error")
        records = list(requestlog.read_request_log(d))
    assert _ids(records) == ["r0", "r1", "r2", "r3"]


def test_damaged_committed_tail_recovers_prefix(tmp_path):
    """A committed TAIL whose crc no longer matches degrades to loud
    line-by-line recovery instead of raising."""
    d = str(tmp_path)
    w = requestlog.RequestLogWriter(d, segment_bytes=1 << 20)
    for i in range(4):
        w.log(_rec(i))
    w.close()
    _, crc, path = requestlog.list_segments(d)[-1]
    assert crc is not None
    with open(path, "ab") as f:
        f.write(b'{"torn')  # crc mismatch + unparsable final line
    with pytest.warns(RuntimeWarning, match="truncated"):
        records = list(requestlog.read_request_log(d))
    assert _ids(records) == [f"r{i}" for i in range(4)]


def test_non_tail_corruption_raises(tmp_path):
    """Damage in the MIDDLE of the log is the unforgivable case: the
    reader raises RequestLogCorruptError, it does not skip."""
    d = str(tmp_path)
    w = requestlog.RequestLogWriter(d, segment_bytes=128)
    for i in range(12):
        w.log(_rec(i))
    w.close()
    segs = requestlog.list_segments(d)
    assert len(segs) >= 2
    _, _, first_path = segs[0]
    blob = bytearray(open(first_path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(first_path, "wb") as f:
        f.write(bytes(blob))
    with pytest.raises(requestlog.RequestLogCorruptError):
        list(requestlog.read_request_log(d))


def test_reader_position_resume(tmp_path):
    """state()/seek() round-trip: a fresh reader seeked to a saved
    position consumes exactly the not-yet-consumed suffix — no repeat,
    no gap — and the state dict drives ft.data.resumable_request_log
    identically."""
    d = str(tmp_path)
    w = requestlog.RequestLogWriter(d, segment_bytes=256)
    for i in range(8):
        w.log(_rec(i))
    w.close()
    assert len(requestlog.list_segments(d)) >= 2

    r1 = requestlog.RequestLogReader(d)
    head = [next(r1) for _ in range(3)]
    st = r1.state()
    assert set(st) == {"epoch", "offset"}

    r2 = requestlog.RequestLogReader(d)
    r2.seek(st)
    tail = list(r2)
    assert _ids(head + tail) == [f"r{i}" for i in range(8)]

    # The ft.data iterator speaks the same position dialect.
    it = resumable_request_log(d)
    assert _ids(list(it)) == [f"r{i}" for i in range(8)]
    it2 = resumable_request_log(d)
    it2.seek(st)
    assert _ids(list(it2)) == [f"r{i}" for i in range(3, 8)]
    # And a position taken on the ft.data side seeks the log reader.
    it3 = resumable_request_log(d)
    for _ in range(5):
        next(it3)
    r3 = requestlog.RequestLogReader(d)
    r3.seek(it3.state())
    assert _ids(list(r3)) == [f"r{i}" for i in range(5, 8)]


def test_seek_past_reaped_segment_is_empty_epoch(tmp_path):
    """Sparse indices (operator-deleted / GC-reaped segments) keep
    positions meaningful: an absent epoch is empty, not an error."""
    d = str(tmp_path)
    w = requestlog.RequestLogWriter(d, segment_bytes=128)
    for i in range(12):
        w.log(_rec(i))
    w.close()
    segs = requestlog.list_segments(d)
    assert len(segs) >= 3
    idx0, _, path0 = segs[0]
    n0 = len(requestlog.segment_records(path0, segs[0][1], False))
    os.remove(path0)
    records = list(requestlog.read_request_log(d))
    assert _ids(records) == [f"r{i}" for i in range(n0, 12)]
    it = resumable_request_log(d)
    it.seek({"epoch": idx0, "offset": 0})
    assert _ids(list(it)) == [f"r{i}" for i in range(n0, 12)]


# ---------------------------------------------------------------------------
# activation: env knob, enable/disable, log_result chokepoint
# ---------------------------------------------------------------------------


def test_knobs_declared():
    for name in (
        "TPUDL_OBS_REQUEST_LOG",
        "TPUDL_OBS_REQUEST_LOG_SEGMENT_BYTES",
        "TPUDL_OBS_REQUEST_LOG_QUEUE",
    ):
        assert name in KNOBS, f"{name} missing from the knob registry"


def test_env_auto_enable_and_knob_sizes(tmp_path, monkeypatch):
    d = str(tmp_path / "rlog")
    monkeypatch.setenv("TPUDL_OBS_REQUEST_LOG", d)
    monkeypatch.setenv("TPUDL_OBS_REQUEST_LOG_SEGMENT_BYTES", "512")
    monkeypatch.setenv("TPUDL_OBS_REQUEST_LOG_QUEUE", "7")
    assert requestlog.active_writer() is not None
    w = requestlog.active_writer()
    assert w.directory == d
    assert w.segment_bytes == 512
    assert w._queue.maxsize == 7
    requestlog.log_result(_rec(0, tenant="a"))
    requestlog.disable()
    assert _ids(list(requestlog.read_request_log(d))) == ["r0"]
    # The chokepoint fed the meter too — same record, same counts.
    assert metering.meter().tenants()["a"]["requests_total"] == 1


def test_log_result_without_writer_still_meters():
    requestlog.log_result(_rec(0, tenant="b", finish_reason="shed_quota"))
    assert requestlog.active_writer() is None
    t = metering.meter().tenants()["b"]
    assert t["requests_total"] == 1
    assert t["sheds"] == {"shed_quota": 1}


# ---------------------------------------------------------------------------
# metering: rollups, render, exporter integration
# ---------------------------------------------------------------------------


def test_meter_rollup_and_shed_bucketing():
    m = metering.TenantMeter()
    m.ingest(_rec(0, tenant="a", tokens_out=7, active_s=2.0,
                  kv_byte_seconds=10.0, adapter_reloads=1))
    m.ingest(_rec(1, tenant="a", finish_reason="shed_slo"))
    m.ingest(_rec(2, tenant="a",
                  finish_reason="failed: RuntimeError: boom"))
    m.ingest(_rec(3))  # tenant None -> _base
    snap = m.tenants()
    a = snap["a"]
    assert a["requests_total"] == 3
    assert a["requests_completed"] == 1
    assert a["tokens_out"] == 7 + 5 + 5
    assert a["sheds"] == {"shed_slo": 1, "failed": 1}
    assert a["chip_seconds"] == pytest.approx(2.0)
    assert a["adapter_residency_s"] == pytest.approx(2.0)
    assert a["adapter_reloads"] == 1
    base = snap[metering.BASE_TENANT]
    assert base["requests_total"] == 1
    # Base-model requests hold no adapter: residency stays 0.
    assert base["adapter_residency_s"] == 0.0


def test_meter_free_text_reasons_stay_closed_set():
    """Both free-text finish_reason families — ``failed: <exc>`` from
    the engine and ``rejected: <exc>`` from the router — collapse to
    ONE sheds bucket each: the Prometheus metric names render() mints
    from sheds keys must not grow per distinct exception message."""
    m = metering.TenantMeter()
    m.ingest(_rec(0, tenant="a",
                  finish_reason="rejected: ValueError: too long"))
    m.ingest(_rec(1, tenant="a",
                  finish_reason="rejected: ValueError: duplicate id"))
    m.ingest(_rec(2, tenant="a",
                  finish_reason="failed: RuntimeError: boom"))
    m.ingest(_rec(3, tenant="a",
                  finish_reason="failed: OSError: disk"))
    assert m.tenants()["a"]["sheds"] == {"rejected": 2, "failed": 2}
    text = m.render()
    assert 'serve_tenant_requests_rejected{tenant="a"} 2' in text
    assert 'serve_tenant_requests_failed{tenant="a"} 2' in text
    assert "ValueError" not in text and "RuntimeError" not in text


def test_meter_render_tenant_labels():
    m = metering.TenantMeter()
    m.ingest(_rec(0, tenant="acme", tokens_out=9))
    m.set_quota_utilization("acme", 0.25)
    text = m.render()
    assert 'serve_tenant_requests_total{tenant="acme"} 1' in text
    assert 'serve_tenant_tokens_total{tenant="acme"} 9' in text
    assert 'serve_tenant_quota_utilization{tenant="acme"} 0.25' in text
    m.ingest(_rec(1, finish_reason="shed_capacity"))
    text = m.render()
    assert (
        'serve_tenant_requests_shed_capacity{tenant="_base"} 1' in text
    )


def test_exporter_appends_tenant_series():
    from tpudl.obs.exporter import ObsExporter

    ex = ObsExporter(port=0)
    clean = ex.metrics_text()
    assert "serve_tenant_" not in clean  # no tenants -> no extra bytes
    requestlog.log_result(_rec(0, tenant="t9"))
    text = ex.metrics_text()
    assert 'serve_tenant_requests_total{tenant="t9"} 1' in text
    assert '# TYPE serve_tenant_requests_total counter' in text


# ---------------------------------------------------------------------------
# report CLI: --tenants cost table, --request durable fallback
# ---------------------------------------------------------------------------


def _write_log(d, records):
    w = requestlog.RequestLogWriter(d, segment_bytes=1 << 20)
    for r in records:
        w.log(r)
    w.close()


def test_tenant_report_and_cli(tmp_path, capsys):
    d = str(tmp_path)
    _write_log(d, [
        _rec(0, tenant="a", tokens_out=10, active_s=3.0),
        _rec(1, tenant="b", tokens_out=4, active_s=1.0),
        _rec(2, tenant="b", finish_reason="shed_quota"),
    ])
    rep = obs_report.build_tenant_report(
        requestlog.read_request_log(d)
    )
    assert rep["records"] == 3
    assert rep["tenants"]["a"]["chip_share"] == pytest.approx(0.75)
    assert rep["tenants"]["b"]["chip_share"] == pytest.approx(0.25)
    table = obs_report.format_tenant_report(rep)
    assert "shed_quota=1" in table

    assert obs_report.main([d, "--tenants"]) == 0
    out = capsys.readouterr().out
    assert "a" in out and "total chip-seconds" in out

    # Run-dir convention: the log under <run>/requestlog resolves too.
    run = tmp_path / "run"
    os.makedirs(run / "requestlog")
    _write_log(str(run / "requestlog"), [_rec(9, tenant="z")])
    assert obs_report.load_request_records([str(run)])[0][
        "request_id"
    ] == "r9"

    assert obs_report.main([str(tmp_path / "empty"), "--tenants"]) == 1


def test_request_cli_durable_fallback(tmp_path, capsys):
    """--request with the span stream gone falls back to the durable
    terminal record instead of erroring."""
    d = str(tmp_path)
    _write_log(d, [_rec(7, tenant="a", finish_reason="length")])
    assert obs_report.find_request_record([d], "r7")["tenant"] == "a"
    assert obs_report.find_request_record([d], "nope") is None
    assert obs_report.main([d, "--request", "r7"]) == 0
    out = capsys.readouterr().out
    assert "durable record" in out and "finish_reason=length" in out
    assert obs_report.main([d, "--request", "nope"]) == 1


def test_span_report_ignores_request_log_segments(tmp_path):
    """A request log nested under an obs dir must not be ingested as
    span records by the span loader's recursive glob: with only
    requests-*.jsonl segments present, the SPAN loader sees no span
    files at all."""
    _write_log(str(tmp_path / "requestlog"), [_rec(0)])
    with pytest.raises(FileNotFoundError, match="no .*jsonl"):
        obs_report.load_records([str(tmp_path)])


# ---------------------------------------------------------------------------
# train numerics telemetry (satellite: loss scale / grad skips / fp8)
# ---------------------------------------------------------------------------


def test_publish_numerics_telemetry():
    from tpudl.train.precision import publish_numerics_telemetry

    publish_numerics_telemetry(None)  # f32 runs pay nothing
    reg = obs_counters.registry()
    assert "train_loss_scale" not in reg.snapshot().get("gauges", {})

    state = {
        "loss_scale": {
            "scale": np.float32(1024.0),
            "skipped": np.int32(3),
        },
        "fp8": {
            "dense": {"x_hist": np.array([2.0, 1.0], np.float32),
                      "x_scale": np.float32(1.0)},
        },
    }
    publish_numerics_telemetry(state)
    snap = reg.snapshot()
    assert snap["gauges"]["train_loss_scale"] == 1024.0
    assert snap["counters"]["train_grad_skipped_total"] == 3
    # Cumulative source, delta-advanced counter: a re-publish of the
    # same state must NOT double-count.
    publish_numerics_telemetry(state)
    assert (
        reg.snapshot()["counters"]["train_grad_skipped_total"] == 3
    )
    h = reg.snapshot()["histograms"]["train_fp8_amax_drift"]
    assert h["count"] == 2  # one ring observed per publish
    assert h["max"] == pytest.approx(0.5)  # (2 - 1) / 2


# ---------------------------------------------------------------------------
# end to end: serve with the log on, reconcile tenants exactly
# ---------------------------------------------------------------------------


def _tenant_requests(tenants, per_tenant, seed):
    """Ragged multi-tenant mix, interleaved round-robin (the
    heterogeneous batch shape: adjacent slots belong to different
    tenants)."""
    from tpudl.serve import Request

    rng = np.random.default_rng(seed)
    return [
        Request(
            request_id=f"rlog-{tenant}-{i}",
            input_ids=rng.integers(
                1, 512, size=int(rng.integers(2, PROMPT_LEN + 1))
            ).tolist(),
            max_new_tokens=int(rng.integers(6, 13)),
            tenant=tenant,
        )
        for i in range(per_tenant)
        for tenant in tenants
    ]


def test_end_to_end_multitenant_reconciliation(tmp_path):
    """The acceptance bar: a multi-tenant serve with the log on, its
    segment size forced small so that the run CROSSES a rotation
    boundary, leaves one record per Result, zero drops, and per-tenant
    token sums from the READER equal to the live Results — and the
    live meter agrees. The flywheel ingest and every per-tenant bill
    stand on this reconciliation."""
    adapters = make_adapters(2, rank=2, seed=0)
    session = build_tenant_session(adapters, num_slots=2)
    reqs = _tenant_requests(list(adapters), per_tenant=3, seed=1)
    writer = requestlog.enable(str(tmp_path), segment_bytes=1024)
    try:
        results = session.serve(reqs)
    finally:
        requestlog.disable()  # commits the open segment
    assert writer.dropped == 0
    assert len(requestlog.list_segments(str(tmp_path))) >= 2
    records = [
        r for r in requestlog.read_request_log(str(tmp_path))
        if str(r["request_id"]).startswith("rlog-")
    ]
    assert len(records) == len(reqs)
    expected, got = Counter(), Counter()
    for req in reqs:
        expected[req.tenant] += len(results[req.request_id].tokens)
    for r in records:
        got[r["tenant"]] += r["tokens_out"]
        assert r["v"] == requestlog.SCHEMA_VERSION
        assert r["site"] == "engine"
        assert r["finish_reason"] in ("eos", "length")
        assert r["tenant"] is not None
        assert r["tokens_out"] > 0
        assert r["active_s"] >= 0.0
        assert r["kv_page_seconds"] >= 0.0
    assert got == expected
    snap = metering.meter().tenants()
    for tenant, want in got.items():
        assert snap[tenant]["tokens_out"] >= want


def test_router_load_report_tenants_and_quota_gauge():
    """Router.load_report() carries the per-tenant quota-utilization
    section and feeds the metering gauge."""
    from tpudl.serve import Replica, Router

    adapters = make_adapters(2, rank=2, seed=0)
    session = build_tenant_session(adapters, num_slots=2)
    names = sorted(adapters)
    router = Router(
        [Replica("r0", session)],
        tenant_classes={names[0]: {"max_inflight_tokens": 64}},
    )
    try:
        rep = router.load_report()
        assert names[0] in rep["tenants"]
        t = rep["tenants"][names[0]]
        assert t["quota_tokens"] == 64
        assert t["inflight_tokens"] == 0
        assert t["quota_utilization"] == 0.0
    finally:
        router.close()
    snap = metering.meter().tenants()
    assert snap[names[0]]["quota_utilization"] == 0.0
    text = metering.render_tenants()
    assert (
        f'serve_tenant_quota_utilization{{tenant="{names[0]}"}} 0'
        in text
    )
