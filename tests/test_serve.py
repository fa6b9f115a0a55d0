"""skelly-serve: persistent multi-tenant service over warm ensemble lanes.

Pins the ISSUE-7 acceptance criteria and the serve subsystem's contracts:

* wire protocol round-trips for EVERY request type + incremental framing
  (one source of truth shared with `listener.py`);
* THE acceptance pin: two concurrent tenants with different configs in the
  same capacity bucket produce trajectories BITWISE matching their
  sequential `System.run` outputs, with zero ``compile`` events after
  warmup (`observed_jit` events through the server's StatsTracer — the
  `test_retrace.py` discipline at the service level);
* admission control: params-contract and capacity-bucket rejections, queue
  depth shedding, queued -> backfill promotion;
* mid-service snapshot/resume: evict a tenant, re-admit from its snapshot,
  combined trajectory bitwise-matches an uninterrupted run;
* `queue_wait_s` admission latency on lane events + `obs summarize`
  reporting it;
* the scheduler's incremental `admit`/`poll`/`evict` API on an
  initially-empty (template-constructed) service.
"""

import io
import json

import numpy as np
import pytest

from skellysim_tpu.builder import build_simulation
from skellysim_tpu.config import Body, BackgroundSource, Config, Fiber, schema
from skellysim_tpu.config.toml_io import dumps as toml_dumps
from skellysim_tpu.io.trajectory import frame_bytes
from skellysim_tpu.serve import protocol
from skellysim_tpu.serve.server import SimulationServer


def _tenant_cfg(shift=0.0, n_nodes=8, n_fibers=1, **param_overrides) -> Config:
    """Tiny free-fiber scene (fast-tier sized, like test_ensemble's)."""
    cfg = Config()
    cfg.params.eta = 1.0
    cfg.params.dt_initial = 0.005
    cfg.params.dt_write = 0.005
    cfg.params.t_final = 0.02
    cfg.params.gmres_tol = 1e-10
    cfg.params.adaptive_timestep_flag = False
    for k, v in param_overrides.items():
        setattr(cfg.params, k, v)
    fibers = []
    for i in range(n_fibers):
        fib = Fiber(n_nodes=n_nodes, length=1.0, bending_rigidity=0.01)
        fib.fill_node_positions(np.array([shift + 0.4 * i, 0.0, 0.0]),
                                np.array([0.0, 0.0, 1.0]))
        fibers.append(fib)
    cfg.fibers = fibers
    cfg.background = BackgroundSource(uniform=[1.0, 0.0, 0.0])
    return cfg


def _toml(cfg: Config) -> str:
    return toml_dumps(schema.unpack(cfg))


def _sequential_frames(cfg: Config) -> list:
    """Reference trajectory: initial frame + System.run boundary frames,
    with the rng_state stamp a CLI-written trajectory carries (serve frames
    carry it too, for resume continuity through `--resume`)."""
    system, state, rng = build_simulation(cfg)
    rs = rng.dump_state() if rng is not None else None
    frames = [frame_bytes(state, rng_state=rs)]
    system.run(state, writer=lambda st, sol, **kw: frames.append(
        frame_bytes(st, rng_state=rs)))
    return frames


@pytest.fixture(scope="module")
def server():
    """One warm 2-lane unroll server shared module-wide (tenant records
    accumulate; each test uses fresh tenant ids)."""
    return SimulationServer(
        _tenant_cfg(), serve_cfg=schema.ServeConfig(max_lanes=2,
                                                    batch_impl="unroll"))


def _submit(server, cfg, **fields):
    resp = server.handle_request({"type": "submit", "config": _toml(cfg),
                                  **fields})
    assert resp["ok"], resp.get("error")
    return resp


def _drain(server, max_rounds=200):
    n = 0
    while server.any_live() and n < max_rounds:
        server.tick()
        n += 1
    assert not server.any_live(), "service did not drain"


def _stream(server, tenant) -> list:
    resp = server.handle_request({"type": "stream", "tenant": tenant})
    assert resp["ok"]
    return [bytes(f) for f in resp["frames"]]


# ------------------------------------------------------------ wire protocol

def test_protocol_roundtrip_every_request_type():
    """Every request type survives make_request -> frame -> decode, through
    the same framing `listener.py` serves over."""
    samples = {
        "submit": dict(config="[params]\n", tenant="t1", t_final=0.5,
                       resume_frame=b"\x81\xa1x\x01"),
        "status": dict(tenant="t1"),
        "stream": dict(tenant="t1", max_frames=3),
        "snapshot": dict(tenant="t1"),
        "cancel": dict(tenant="t1"),
        "stats": {},
        "chaos": dict(action="nan_lane", tenant="t1"),
        "shutdown": {},
    }
    assert set(samples) == set(protocol.REQUEST_FIELDS)
    for rtype, fields in samples.items():
        req = protocol.make_request(rtype, **fields)
        buf = io.BytesIO()
        protocol.write_message(buf, req)
        buf.seek(0)
        back = protocol.read_message(buf)
        assert back == req, rtype
        assert protocol.validate_request(back) is None


def test_protocol_framing_edges():
    # zero-length control frame round-trips distinctly from EOF
    buf = io.BytesIO()
    protocol.write_empty(buf)
    buf.seek(0)
    assert protocol.read_frame(buf) == b""
    assert protocol.read_frame(buf) is None  # EOF
    # truncated payload = disconnect, not an exception
    buf = io.BytesIO(protocol.HEADER.pack(10) + b"abc")
    assert protocol.read_frame(buf) is None


def test_frame_decoder_incremental():
    """Byte-at-a-time feeding reassembles exactly the sent frames (the
    non-blocking socket path)."""
    msgs = [{"type": "stats"}, {"type": "status", "tenant": "t9"}]
    wire = b"".join(
        protocol.HEADER.pack(len(p)) + p
        for p in [protocol.pack_message(m) for m in msgs]) \
        + protocol.HEADER.pack(0)
    dec = protocol.FrameDecoder()
    out = []
    for i in range(len(wire)):
        out.extend(dec.feed(wire[i:i + 1]))
    assert [protocol.unpack_message(p) for p in out[:2]] == msgs
    assert out[2] == b""


def test_validate_request_rejections():
    assert "unknown request type" in protocol.validate_request({"type": "x"})
    assert "missing required" in protocol.validate_request({"type": "status"})
    assert "unknown field" in protocol.validate_request(
        {"type": "stats", "bogus": 1})
    assert "msgpack map" in protocol.validate_request([1, 2])


def test_serve_config_loading(tmp_path):
    p = tmp_path / "cfg.toml"
    p.write_text("[serve]\nmax_lanes = 3\nbucket_capacities = [2, 4]\n"
                 "queue_depth = 5\n")
    sc = schema.load_serve_config(str(p))
    assert (sc.max_lanes, sc.bucket_capacities, sc.queue_depth) == (3, [2, 4], 5)
    p.write_text("[serve]\nmax_lens = 3\n")
    with pytest.raises(ValueError, match="unknown \\[serve\\] keys"):
        schema.load_serve_config(str(p))
    p.write_text("[serve]\nbatch_impl = 'nope'\n")
    with pytest.raises(ValueError, match="batch_impl"):
        schema.load_serve_config(str(p))


# --------------------------------------------------- the acceptance criteria

def test_two_tenants_bitwise_parity_zero_compiles_after_warm(server):
    """THE acceptance pin: two concurrent tenants with different configs in
    the same capacity bucket; per-tenant frame streams BITWISE identical to
    their sequential System.run trajectories; zero compile events after
    warmup (observed_jit events through the server tracer)."""
    assert server.metrics.warm and server.metrics.compiles >= 1
    compiles_at_warm = server.metrics.compiles

    shifts = (0.1, 0.3)
    resp = [_submit(server, _tenant_cfg(s)) for s in shifts]
    assert [r["lane"] for r in resp] == [0, 1]  # concurrent, same bucket
    assert len({r["bucket"] for r in resp}) == 1
    _drain(server)

    for r, s in zip(resp, shifts):
        got = _stream(server, r["tenant"])
        assert len(got) >= 3
        assert got == _sequential_frames(_tenant_cfg(s))
        st = server.handle_request({"type": "status", "tenant": r["tenant"]})
        assert st["status"] == "finished" and st["t"] <= st["t_final"]

    assert server.metrics.compiles == compiles_at_warm
    assert server.metrics.stats()["compiles_after_warm"] == 0


def test_snapshot_evict_resume_matches_uninterrupted(server):
    """Satellite pin: evict a tenant mid-service, re-admit from its
    snapshot — pre-eviction + post-resume frames bitwise-match an
    uninterrupted run's."""
    cfg = _tenant_cfg(0.7)
    r = _submit(server, cfg)
    server.tick()
    server.tick()
    snap = server.handle_request({"type": "snapshot", "tenant": r["tenant"]})
    assert snap["ok"] and snap["status"] == "running"
    # graceful eviction (the disconnect path drives the same _release)
    server.handle_request({"type": "cancel", "tenant": r["tenant"]})
    pre = _stream(server, r["tenant"])
    st = server.handle_request({"type": "status", "tenant": r["tenant"]})
    assert st["status"] == "cancelled"

    r2 = server.handle_request({
        "type": "submit", "config": _toml(cfg),
        "resume_frame": bytes(snap["frame"])})
    assert r2["ok"], r2.get("error")
    _drain(server)
    post = _stream(server, r2["tenant"])
    assert pre + post == _sequential_frames(cfg)
    assert server.metrics.stats()["compiles_after_warm"] == 0


def test_disconnect_evicts_and_snapshot_survives(server):
    """A client disconnect gracefully evicts its tenants: lane freed, final
    snapshot retained for a later resume."""
    conn = object()
    r = _submit(server, _tenant_cfg(0.9))
    # hand ownership to a fake connection, then drop it
    server.registry.get(r["tenant"]).conn = conn
    server.tick()
    server.evict_conn(conn)
    st = server.handle_request({"type": "status", "tenant": r["tenant"]})
    assert st["status"] == "evicted" and st["lane"] is None
    snap = server.handle_request({"type": "snapshot", "tenant": r["tenant"]})
    assert snap["ok"] and snap["t"] > 0.0
    assert not server.any_live()


# ----------------------------------------------------------- admission rules

def test_params_contract_rejection(server):
    resp = server.handle_request({
        "type": "submit", "config": _toml(_tenant_cfg(gmres_tol=1e-6))})
    assert not resp["ok"] and "gmres_tol" in resp["error"]
    resp = server.handle_request({
        "type": "submit",
        "config": _toml(_tenant_cfg(0.1, t_final=0.01, seed=7)),
        "t_final": 0.01})
    # seed/t_final are the per-tenant exceptions — this one must admit
    assert resp["ok"], resp.get("error")
    _drain(server)


def test_bucket_mismatch_rejection(server):
    resp = server.handle_request({
        "type": "submit", "config": _toml(_tenant_cfg(n_nodes=16))})
    assert not resp["ok"] and "bucket" in resp["error"]
    resp = server.handle_request({
        "type": "submit", "config": _toml(_tenant_cfg(n_fibers=3))})
    assert not resp["ok"]
    assert server.metrics.rejected >= 2


def test_tenant_config_validation(server):
    for bad, needle in [
        ("not toml [", "parse error"),
        ("[params]\nt_final = 0.02\n", "no fibers"),
    ]:
        resp = server.handle_request({"type": "submit", "config": bad})
        assert not resp["ok"] and needle in resp["error"]


def test_queue_depth_sheds_and_backfills(server):
    """Admission control: lanes full -> queue; queue full -> structured
    rejection with retry=True; drained lanes backfill from the queue."""
    rs = [_submit(server, _tenant_cfg(0.05 * i)) for i in range(3)]
    assert rs[2]["queued"] and rs[2]["lane"] is None
    st = server.handle_request({"type": "status", "tenant": rs[2]["tenant"]})
    assert st["status"] == "queued"

    depth = server.serve_cfg.queue_depth
    server.serve_cfg.queue_depth = 1  # one slot, already taken by rs[2]
    try:
        resp = server.handle_request({
            "type": "submit", "config": _toml(_tenant_cfg(0.9))})
        assert not resp["ok"] and resp.get("retry") is True
    finally:
        server.serve_cfg.queue_depth = depth

    _drain(server)
    for r in rs:
        st = server.handle_request({"type": "status", "tenant": r["tenant"]})
        assert st["status"] == "finished"
        assert len(_stream(server, r["tenant"])) >= 3


def test_cancel_queued_tenant(server):
    rs = [_submit(server, _tenant_cfg(0.05 * i)) for i in range(3)]
    assert rs[2]["queued"]
    resp = server.handle_request({"type": "cancel",
                                  "tenant": rs[2]["tenant"]})
    assert resp["ok"] and resp["status"] == "cancelled"
    # releasing a QUEUED tenant keeps its spec state as the snapshot — a
    # resumed submit buffers no initial frame, so dropping the spec
    # without this would lose the tenant's resume point entirely
    snap = server.handle_request({"type": "snapshot",
                                  "tenant": rs[2]["tenant"]})
    assert snap["ok"] and snap["t"] == 0.0
    _drain(server)
    done = [server.handle_request({"type": "status", "tenant": r["tenant"]})
            ["status"] for r in rs]
    assert done == ["finished", "finished", "cancelled"]


def test_record_ttl_expires_terminal_records(server):
    """`[serve] record_ttl_s`: terminal tenant records expire that long
    after retirement (bounded retention — docs/serving.md); live tenants
    and records inside the TTL survive; 0 (the default) disables expiry."""
    r = _submit(server, _tenant_cfg(0.55), t_final=0.0)
    _drain(server)
    tid = r["tenant"]
    assert server.handle_request({"type": "status", "tenant": tid})["ok"]
    old_ttl = server.serve_cfg.record_ttl_s
    try:
        server.serve_cfg.record_ttl_s = 60.0
        server.tick()                      # inside the TTL: record survives
        assert server.handle_request({"type": "status", "tenant": tid})["ok"]
        # age the record past the TTL instead of sleeping (fast tier)
        server.registry.get(tid).retired_at -= 120.0
        resp = server.handle_request({"type": "status", "tenant": tid})
        assert not resp["ok"] and "unknown tenant" in resp["error"]
        # a live (running/queued) tenant has no retirement clock at all
        r2 = _submit(server, _tenant_cfg(0.6))
        assert server.registry.get(r2["tenant"]).retired_at is None
        _drain(server)
        assert server.handle_request(
            {"type": "status", "tenant": r2["tenant"]})["ok"]
    finally:
        server.serve_cfg.record_ttl_s = old_ttl


def test_explicit_zero_t_final(server):
    """A requested t_final of 0.0 is honored (no falsy substitution of the
    config's): the tenant admits and retires without stepping."""
    r = _submit(server, _tenant_cfg(0.4), t_final=0.0)
    _drain(server)
    st = server.handle_request({"type": "status", "tenant": r["tenant"]})
    assert st["status"] == "finished" and st["steps"] == 0


def test_stats_shape_and_stream_accounting(server):
    stats = server.handle_request({"type": "stats"})["stats"]
    for key in ("admitted", "rejected", "retired", "retire_reasons",
                "rounds", "steps", "steps_per_s", "mean_occupancy",
                "admission_wait_s", "compiles", "compiles_after_warm",
                "warm", "frames_streamed", "frames_streamed_total",
                "tenants", "buckets",
                # skelly-pulse SLO histograms (docs/serving.md)
                "round_wall_s_hist", "frame_stream_s", "histograms"):
        assert key in stats, key
    assert stats["warm"] is True
    assert stats["buckets"][0]["lanes"] == 2
    assert stats["frames_streamed_total"] >= 3
    assert stats["admission_wait_s"]["n"] == stats["admitted"]
    # percentile read-out from the folded events, ordered as percentiles
    for key in ("admission_wait_s", "round_wall_s_hist", "frame_stream_s"):
        slo = stats[key]
        assert slo["p50"] <= slo["p95"] <= slo["p99"], (key, slo)
    assert stats["round_wall_s_hist"]["n"] == stats["rounds"] > 0
    assert stats["frame_stream_s"]["n"] >= 1
    # the prometheus text page renders from the same payload
    from skellysim_tpu.serve import protocol

    prom = protocol.render_prometheus(stats)
    assert "skellysim_serve_round_wall_seconds_bucket" in prom
    assert 'le="+Inf"' in prom
    assert prom.strip().splitlines()[-1].startswith(
        "skellysim_serve_frame_stream_seconds_count")


def test_unknown_tenant_and_malformed_requests(server):
    assert "unknown tenant" in server.handle_request(
        {"type": "status", "tenant": "nope"})["error"]
    assert "unknown request type" in server.handle_request(
        {"type": "gibberish"})["error"]


# ------------------------------------------------- queue_wait_s + summarize

def test_queue_wait_on_lane_events_and_summarize(server):
    """Lane admit/backfill events carry queue_wait_s (admission latency);
    `obs summarize` folds them into the lane table."""
    lane_events = [e for e in server.tracer.events if e["ev"] == "lane"
                   and e["action"] in ("admit", "backfill")]
    assert lane_events, "no lane admissions recorded"
    assert all("queue_wait_s" in e and e["queue_wait_s"] >= 0.0
               for e in lane_events)
    # a queued tenant (lanes were busy) must show a strictly positive wait
    assert any(e["queue_wait_s"] > 0.0 for e in lane_events
               if e["action"] == "backfill")

    from skellysim_tpu.obs.summarize import Summary

    s = Summary()
    for e in server.tracer.events:
        s.add_line(json.dumps(e))
    report = s.render()
    assert "admission wait:" in report
    assert "ensemble lanes" in report


# -------------------------------------------- scheduler incremental service

def test_scheduler_template_admit_poll_evict():
    """The incremental API directly: an initially-EMPTY scheduler built
    from a template, members admitted/evicted between polls, one trace."""
    from skellysim_tpu.ensemble import EnsembleRunner, EnsembleScheduler
    from skellysim_tpu.ensemble.scheduler import MemberSpec
    from skellysim_tpu.testing import trace_counting_jit

    system, state, _ = build_simulation(_tenant_cfg())
    runner = EnsembleRunner(system)
    step = trace_counting_jit(runner.step_impl)
    sched = EnsembleScheduler(runner, [], 2, template=state, step_fn=step)
    assert sched.poll() == [] and sched.rounds == 0  # idle no-op

    lane = sched.admit(MemberSpec(member_id="a", state=state, t_final=0.02))
    assert lane == 0 and sched.live == 1
    sched.poll()
    assert sched.admit(MemberSpec(member_id="b", state=_tenant_state(0.2),
                                  t_final=0.02)) == 1
    mid = sched.evict(0, reason="evicted")
    assert float(mid.time) > 0.0 and sched.lane_of("a") is None
    # evicted lane state resumes exactly: re-admit and drain both
    assert sched.admit(MemberSpec(member_id="a2", state=mid,
                                  t_final=0.02)) == 0
    sched.run()
    assert set(sched.retired) == {"a", "b", "a2"}
    assert step.trace_count == 1, "incremental service retraced"


def _tenant_state(shift):
    _, state, _ = build_simulation(_tenant_cfg(shift))
    return state


# --------------------------------------------------------- padded admission

@pytest.mark.slow  # second compiled bucket program (own capacity)
def test_padded_bucket_admission_parity():
    """A 1-fiber tenant admits into a capacity-2 bucket (inert masked
    padding); its streamed trajectory matches the unpadded sequential run
    to roundoff, and frames carry only the ACTIVE fibers."""
    srv = SimulationServer(
        _tenant_cfg(), serve_cfg=schema.ServeConfig(
            max_lanes=2, bucket_capacities=[2], batch_impl="unroll"))
    cfg = _tenant_cfg(0.2)
    r = _submit(srv, cfg)
    assert r["bucket"] == 2
    _drain(srv)
    got = _stream(srv, r["tenant"])
    seq = _sequential_frames(cfg)
    assert len(got) == len(seq)
    for gb, sb in zip(got, seq):
        g = protocol.unpack_message(gb)
        s = protocol.unpack_message(sb)
        assert len(g["fibers"][1]) == 1  # active fibers only on the wire
        np.testing.assert_allclose(
            np.asarray(g["fibers"][1][0]["x_"]),
            np.asarray(s["fibers"][1][0]["x_"]), rtol=0, atol=1e-10)
    assert srv.metrics.stats()["compiles_after_warm"] == 0


# ------------------------------------------------------------ socket + CLI

# ------------------------------------------------ skelly-guard robustness

def test_frame_decoder_oversized_header_survives():
    """A header past the bound yields the OversizedFrame sentinel
    IMMEDIATELY, the declared bytes are skipped as they arrive, and
    framing resynchronizes on the next real frame — byte-at-a-time."""
    dec = protocol.FrameDecoder(max_frame_bytes=64)
    payload = protocol.pack_message({"type": "stats"})
    wire = (protocol.HEADER.pack(100) + b"x" * 100
            + protocol.HEADER.pack(len(payload)) + payload)
    out = []
    for i in range(len(wire)):
        out.extend(dec.feed(wire[i:i + 1]))
    assert isinstance(out[0], protocol.OversizedFrame)
    assert out[0].size == 100
    assert protocol.unpack_message(out[1]) == {"type": "stats"}
    assert dec.oversized == 1


def test_frame_decoder_boundary_sizes():
    """Exactly-at-bound frames pass; one byte over trips the sentinel."""
    dec = protocol.FrameDecoder(max_frame_bytes=32)
    exact = b"a" * 32
    assert dec.feed(protocol.HEADER.pack(32) + exact) == [exact]
    out = dec.feed(protocol.HEADER.pack(33) + b"b" * 33)
    assert len(out) == 1 and isinstance(out[0], protocol.OversizedFrame)
    # after the skip the decoder is clean again
    assert dec.feed(protocol.HEADER.pack(32) + exact) == [exact]


def test_frame_decoder_truncated_then_completed():
    dec = protocol.FrameDecoder()
    payload = protocol.pack_message({"type": "stats"})
    framed = protocol.HEADER.pack(len(payload)) + payload
    assert dec.feed(framed[:5]) == []
    assert dec.feed(framed[5:]) == [payload]


def test_frame_decoder_garbage_stream_does_not_raise():
    """Random bytes whose fake header claims an absurd size park the
    decoder in skip mode (framing cannot resync inside garbage) — but
    never raise: the server answers an error and stays up."""
    from skellysim_tpu.guard import chaos as chaos_mod

    dec = protocol.FrameDecoder()
    garbage = chaos_mod.garble_frame(bytes(64), seed=7, flips=64)
    out = dec.feed(garbage)
    assert all(isinstance(f, (bytes, protocol.OversizedFrame))
               for f in out)


class _StubConn:
    """Scripted socket for `_service_conn` (recv once, capture sends)."""

    def __init__(self, data: bytes):
        self._data = data
        self.sent = b""

    def recv(self, n):
        d, self._data = self._data, b""
        return d

    def sendall(self, b):
        self.sent += b

    def close(self):
        pass


class _StubSel:
    def unregister(self, c):
        pass


def _served_responses(server, wire: bytes, max_frame_bytes=None):
    conn = _StubConn(wire)
    dec = (protocol.FrameDecoder(max_frame_bytes=max_frame_bytes)
           if max_frame_bytes else protocol.FrameDecoder())
    decoders = {conn: dec}
    server._service_conn(conn, decoders, _StubSel())
    out = protocol.FrameDecoder().feed(conn.sent)
    return conn, decoders, [protocol.unpack_message(f) for f in out]


def test_server_survives_garbled_frame(server):
    """Satellite pin: a well-framed but undecodable request answers a
    structured error and the connection survives."""
    from skellysim_tpu.guard import chaos as chaos_mod

    garbled = chaos_mod.garble_frame(
        protocol.pack_message({"type": "stats"}), seed=1)
    wire = protocol.HEADER.pack(len(garbled)) + garbled
    conn, decoders, resps = _served_responses(server, wire)
    assert resps and resps[0]["ok"] is False
    assert "undecodable" in resps[0]["error"]
    assert conn in decoders  # NOT dropped
    # and a valid request on the same (surviving) connection still works
    valid = protocol.pack_message({"type": "stats"})
    conn2 = _StubConn(protocol.HEADER.pack(len(valid)) + valid)
    decoders[conn2] = decoders.pop(conn)
    server._service_conn(conn2, decoders, _StubSel())
    ok = protocol.unpack_message(protocol.FrameDecoder().feed(conn2.sent)[0])
    assert ok["ok"] is True


def test_server_survives_oversized_frame(server):
    """Satellite pin: an oversized header answers a structured error
    (flagged ``oversized``) without waiting for the body, and the
    connection survives."""
    wire = protocol.HEADER.pack(1 << 40)
    conn, decoders, resps = _served_responses(server, wire)
    assert resps and resps[0]["ok"] is False
    assert resps[0].get("oversized") is True
    assert conn in decoders
    assert server.metrics.faults.get("frame_oversized", 0) >= 1


def test_chaos_request_gated_off_by_default(server):
    resp = server.handle_request({"type": "chaos", "action": "nan_lane",
                                  "tenant": "whatever"})
    assert resp["ok"] is False and "chaos_enabled" in resp["error"]


def _nan_pair(server, shift_a, shift_b):
    """Submit two tenants into one bucket, run one healthy round, poison
    A's lane; returns (tenant_a, tenant_b) after the drain."""
    from skellysim_tpu.guard import chaos as chaos_mod

    ra = _submit(server, _tenant_cfg(shift_a))
    rb = _submit(server, _tenant_cfg(shift_b))
    server.tick()   # one healthy round for both
    chaos_mod.nan_lane_of(server.buckets[0].scheduler, ra["tenant"])
    _drain(server)
    return ra["tenant"], rb["tenant"]


def test_nan_tenant_fails_sibling_finishes(server):
    """ISSUE-9 acceptance pin, fast half: a NaN injected into tenant A's
    lane yields status=failed for A with a nonzero nonfinite verdict —
    surfaced in status/stats, a structured terminal stream, never a hang
    — while its bucket sibling finishes healthy. (The sibling's BITWISE
    sequential parity is the slow half below; cross-lane bitwise
    isolation is also pinned cheaply in test_ensemble.py.)"""
    from skellysim_tpu.guard import verdict

    ta, tb = _nan_pair(server, 0.25, 0.45)
    sa = server.handle_request({"type": "status", "tenant": ta})
    assert sa["status"] == "failed"
    assert sa["health"] & verdict.NONFINITE
    assert "nonfinite" in sa["verdict"]
    sb = server.handle_request({"type": "status", "tenant": tb})
    assert sb["status"] == "finished" and sb["health"] == 0
    # failed tenant: structured terminal stream, not a hang
    resp = server.handle_request({"type": "stream", "tenant": ta})
    assert resp["ok"] and resp["eof"] is True
    stats = server.metrics.stats()
    assert stats["retire_reasons"].get("failed", 0) >= 1
    assert stats["faults"].get("lane_failed", 0) >= 1
    assert stats["compiles_after_warm"] == 0


@pytest.mark.slow  # sequential-reference System build + run
def test_nan_tenant_sibling_streams_bitwise(server):
    """ISSUE-9 acceptance pin, slow half: the surviving sibling's streamed
    trajectory is BITWISE equal to its uninterrupted sequential
    `System.run` output."""
    cfg_b = _tenant_cfg(0.65)
    ta, tb = _nan_pair(server, 0.6, 0.65)
    sa = server.handle_request({"type": "status", "tenant": ta})
    assert sa["status"] == "failed"
    assert _stream(server, tb) == _sequential_frames(cfg_b)
    assert server.metrics.stats()["compiles_after_warm"] == 0


def test_status_surfaces_loss_of_accuracy_and_dt_underflow_fields(server):
    """Satellite pin: the `/status` schema carries the solver-health
    fields (they used to die in the metrics JSONL)."""
    r = _submit(server, _tenant_cfg(0.55))
    _drain(server)
    st = server.handle_request({"type": "status", "tenant": r["tenant"]})
    assert st["ok"]
    for key in ("health", "verdict", "loss_of_accuracy_steps",
                "dt_underflow"):
        assert key in st, key
    assert st["health"] == 0 and st["verdict"] == []
    assert st["dt_underflow"] is False


def test_journal_roundtrip_and_torn_tail(tmp_path):
    """Write-ahead journal: latest-wins replay, terminal entries inherit
    the last snapshot, and a torn final frame (crash mid-append) loses
    only that frame."""
    from skellysim_tpu.serve.journal import TenantJournal, replay

    p = tmp_path / "j.bin"
    with TenantJournal(str(p)) as j:
        j.record("admit", "tA", bucket=1, t_final=0.5, status="queued",
                 frame=b"F0")
        j.record("checkpoint", "tA", bucket=1, t_final=0.5,
                 status="running", frame=b"F1", t=0.25)
        j.record("admit", "tB", bucket=1, t_final=0.5, status="queued",
                 frame=b"G0")
        j.record("retire", "tB", bucket=1, t_final=0.5, status="finished",
                 t=0.5, health=0)
    entries = replay(str(p))
    assert entries["tA"]["status"] == "running"
    assert bytes(entries["tA"]["frame"]) == b"F1"
    assert entries["tB"]["status"] == "finished"
    # terminal entry without a frame inherits the last snapshot
    assert bytes(entries["tB"]["frame"]) == b"G0"

    data = p.read_bytes()
    p.write_bytes(data[:-3])  # tear the final frame
    entries2 = replay(str(p))
    assert entries2["tA"]["status"] == "running"
    assert entries2["tB"]["status"] == "queued"  # retire entry was torn

    assert replay(str(tmp_path / "missing.bin")) == {}


def _journal_entry_count(path) -> int:
    n = 0
    with open(path, "rb") as fh:
        while True:
            try:
                buf = protocol.read_frame(fh)
            except ValueError:
                break
            if not buf:
                break
            n += 1
    return n


def test_journal_recovery_evicts_unreadmittable_live_records(tmp_path):
    """A live-status journal record whose bucket no longer exists on the
    restarted server must restore as terminal `evicted` — never a zombie
    `running` tenant no scheduler drives (clients would poll it
    forever)."""
    from skellysim_tpu.serve.journal import TenantJournal

    wal = tmp_path / "wal.bin"
    with TenantJournal(str(wal)) as j:
        # bucket that does not exist on the restarted server
        j.record("checkpoint", "ghost", bucket=999, t_final=1.0,
                 status="running", frame=b"not-a-real-frame", t=0.5)
        # right bucket (capacity 1 = the base fiber count), junk snapshot:
        # the decode failure must degrade, not make the server unbootable
        j.record("checkpoint", "junk", bucket=1, t_final=1.0,
                 status="running", frame=b"also-not-a-frame", t=0.5)
    srv = SimulationServer(
        _tenant_cfg(), warmup=False,
        serve_cfg=schema.ServeConfig(max_lanes=2, batch_impl="unroll",
                                     journal_path=str(wal)))
    for tid in ("ghost", "junk"):
        st = srv.handle_request({"type": "status", "tenant": tid})
        assert st["ok"] and st["status"] == "evicted", (tid, st)
    assert not srv.any_live()
    # and the compacted journal carries exactly one record per tenant
    assert _journal_entry_count(str(wal)) == 2


@pytest.mark.slow  # builds two fresh servers (cold compiles)
def test_journal_crash_recovery_matches_unkilled_run(tmp_path):
    """ISSUE-9 acceptance pin, in-process: abandon a journaling server
    mid-flight (the kill -9 analogue — nothing is flushed beyond what the
    WAL already wrote), restart on the same journal, and the re-admitted
    tenant finishes with a final state BITWISE equal to the uninterrupted
    run's; terminal records survive too."""
    cfg = _tenant_cfg(0.35)
    scfg = schema.ServeConfig(max_lanes=2, batch_impl="unroll",
                              journal_path=str(tmp_path / "wal.bin"),
                              journal_every=2)
    srv = SimulationServer(cfg, serve_cfg=scfg)
    r = _submit(srv, cfg)
    tid = r["tenant"]
    done = _submit(srv, _tenant_cfg(0.05))
    srv.tick()
    srv.tick()
    srv.tick()   # tenant mid-flight, >= 1 checkpoint written
    st = srv.handle_request({"type": "status", "tenant": tid})
    assert st["status"] == "running" and 0.0 < st["t"] < st["t_final"]
    srv.journal.close()   # abandon srv: its in-memory state dies here

    srv2 = SimulationServer(cfg, serve_cfg=scfg)
    # recovery COMPACTED the journal: exactly one entry per known tenant
    assert _journal_entry_count(scfg.journal_path) == 2
    st2 = srv2.handle_request({"type": "status", "tenant": tid})
    assert st2["ok"] and st2["status"] in ("queued", "running")
    assert st2["t"] <= st["t"]  # replays from the checkpoint, never ahead
    _drain(srv2)
    st3 = srv2.handle_request({"type": "status", "tenant": tid})
    assert st3["status"] == "finished"
    # the resumed final state == the uninterrupted run's final state
    snap = srv2.handle_request({"type": "snapshot", "tenant": tid})
    assert bytes(snap["frame"]) == _sequential_frames(cfg)[-1]
    assert srv2.handle_request(
        {"type": "stats"})["stats"]["journal"] is True
    del done


@pytest.mark.slow  # subprocess server boot (compile) + TCP round-trips
def test_socket_end_to_end(tmp_path):
    """The CI smoke's contract, in-tree: spawn `python -m
    skellysim_tpu.serve`, admit two tenants over TCP, stream >= 2 frames
    each, clean shutdown with exit code 0."""
    import os
    import subprocess
    import sys

    from skellysim_tpu.serve.client import SpawnedServer

    cfg_path = str(tmp_path / "serve_config.toml")
    base = _tenant_cfg()
    base.save(cfg_path)
    with open(cfg_path, "a") as fh:
        fh.write("\n[serve]\nmax_lanes = 2\nbatch_impl = \"unroll\"\n")

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo  # the repo only: no inherited site hooks
    with SpawnedServer(cfg_path, env=env) as srv:
        with srv.client() as c:
            tids = [c.submit(_toml(_tenant_cfg(s)))["tenant"]
                    for s in (0.1, 0.3)]
            for tid in tids:
                st = c.wait(tid, timeout=120)
                assert st["status"] == "finished"
                frames = c.stream(tid)["frames"]
                assert len(frames) >= 2
            stats = c.stats()
            assert stats["compiles_after_warm"] == 0
            # skelly-pulse SLO histograms, folded from REAL events over
            # the wire: admission wait + round wall distributions report
            # percentiles, and the prometheus rendering carries them
            for key in ("admission_wait_s", "round_wall_s_hist",
                        "frame_stream_s"):
                slo = stats[key]
                for q in ("p50", "p95", "p99"):
                    assert q in slo, (key, slo)
                assert slo["p50"] <= slo["p95"] <= slo["p99"]
            assert stats["admission_wait_s"]["n"] == stats["admitted"] == 2
            assert stats["round_wall_s_hist"]["n"] == stats["rounds"] > 0
            assert stats["frame_stream_s"]["n"] >= 2  # one drain per tenant
            prom = c.stats_prometheus()
            assert "skellysim_serve_admission_wait_seconds_bucket" in prom
            assert 'le="+Inf"' in prom
            assert "skellysim_serve_compiles_after_warm_total 0" in prom
        rc = srv.stop()
    assert rc == 0


# --------------------------------------------- dynamic-instability serving


def _di_cfg(n_sites=4, nucleation_rate=200.0, t_final=0.04, seed=130319):
    """Fiber-less confined-class DI tenant scene: one analytic nucleating
    body with EMBEDDED sites (the wire contract — docs/scenarios.md)."""
    cfg = Config()
    cfg.params.eta = 1.0
    cfg.params.dt_initial = 0.02
    cfg.params.dt_write = 0.02
    cfg.params.t_final = t_final
    cfg.params.gmres_tol = 1e-10
    cfg.params.adaptive_timestep_flag = False
    cfg.params.seed = seed
    di = cfg.params.dynamic_instability
    di.n_nodes = 8
    di.v_growth = 0.2
    di.f_catastrophe = 0.0
    di.nucleation_rate = nucleation_rate
    di.min_length = 0.3
    di.radius = 0.0125
    di.bending_rigidity = 0.01
    rng = np.random.default_rng(7)
    sites = rng.standard_normal((n_sites, 3))
    sites = 0.4 * sites / np.linalg.norm(sites, axis=1, keepdims=True)
    cfg.bodies = [Body(shape="sphere", radius=0.4, n_nodes=40,
                       n_nucleation_sites=n_sites,
                       nucleation_sites=sites.ravel().tolist())]
    return cfg


def test_di_tenant_admission_rules():
    """DI serve admission (docs/scenarios.md): bodies stay rejected on a
    non-DI server; a DI server admits ANALYTIC bodies with embedded sites
    and rejects non-analytic surfaces and unembedded generated sites."""
    from skellysim_tpu.serve import tenants as tenants_mod

    text = _toml(_di_cfg())
    with pytest.raises(ValueError, match="dynamic"):
        tenants_mod.parse_tenant_config(text, di_enabled=False)
    out = tenants_mod.parse_tenant_config(text, di_enabled=True)
    assert out.bodies and out.bodies[0].nucleation_sites
    bad = _di_cfg()
    bad.bodies[0].shape = "deformable"
    with pytest.raises(ValueError, match="analytic"):
        tenants_mod.parse_tenant_config(_toml(bad), di_enabled=True)
    bad2 = _di_cfg()
    bad2.bodies[0].nucleation_sites = []
    with pytest.raises(ValueError, match="embed"):
        tenants_mod.parse_tenant_config(_toml(bad2), di_enabled=True)
    # fiber-less is legal ONLY with a nucleating body on a DI server
    nofib = _di_cfg()
    nofib.bodies = []
    with pytest.raises(ValueError, match="no fibers"):
        tenants_mod.parse_tenant_config(_toml(nofib), di_enabled=True)


@pytest.mark.slow  # warms two vmap coupled body-program buckets (~80 s)
def test_di_tenant_growth_reseat_and_finish():
    """Tentpole serve pin: a DI tenant (fiber-less, nucleating analytic
    body) admits onto a DI server, its nucleation burst outgrows the first
    capacity bucket, `_grow_tenant` reseats it onto the next bucket, and
    it finishes with a streamable trajectory + `growth_reseats` on
    /stats."""
    srv = SimulationServer(
        _di_cfg(), serve_cfg=schema.ServeConfig(max_lanes=1,
                                                batch_impl="vmap",
                                                bucket_capacities=[2, 4]))
    assert srv.di_enabled
    assert [b.capacity for b in srv.buckets] == [2, 4]
    r = _submit(srv, _di_cfg(seed=7), tenant="di0")
    assert r["tenant"] == "di0"
    _drain(srv)
    st = srv.handle_request({"type": "status", "tenant": "di0"})
    assert st["ok"] and st["status"] == "finished", st
    # 4 free sites at rate 200 make the first nucleation burst ~surely
    # outgrow the 2-slot bucket: the growth reseat moved the tenant 2 -> 4
    stats = srv.handle_request({"type": "stats"})["stats"]
    assert stats["growth_reseats"] >= 1, stats
    t = srv.registry.get("di0")
    assert t.bucket == 4
    frames = _stream(srv, "di0")
    assert len(frames) >= 2
    # the snapshot survives as a resume point with its RNG streams
    snap = srv.handle_request({"type": "snapshot", "tenant": "di0"})
    assert snap["ok"] and snap["frame"]
