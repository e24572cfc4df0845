"""Role-split SPDC API (DESIGN.md §7): wire-format round-trips, the
no-plaintext trust boundary, transport equivalence (inline vs threadpool
vs multiprocess), and the multiprocess acceptance end-to-end — N=4 real
worker processes, a tampering server localized and healed via
re-dispatched ShardTasks, det matching the honest run at rtol 1e-10."""
import inspect

import numpy as np
import pytest

from repro.api import (
    AcceleratorHeld,
    BoundaryViolation,
    EdgeServer,
    FaultPlanFrame,
    InlineTransport,
    MultiprocessTransport,
    ShardResult,
    ShardTask,
    SPDCClient,
    ThreadPoolTransport,
    TransportError,
    TransportTimeout,
    WireError,
    decode_message,
    resolve_transport,
)
from repro.api import wire
from repro.core import (
    Determinant,
    ServerFault,
    Verdict,
    authenticate,
    lu_nserver,
    outsource_determinant,
)

N = 4


def _wellcond(n, seed=0, batch=None, dtype=np.float64):
    rng = np.random.default_rng(seed)
    if batch is None:
        return (rng.standard_normal((n, n)) + n * np.eye(n)).astype(dtype)
    return (rng.standard_normal((batch, n, n))
            + n * np.eye(n)).astype(dtype)


# ------------------------------------------------------------- wire format
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("batch", [None, 3])
def test_wire_roundtrip_shard_task(dtype, batch):
    x_row = _wellcond(8, seed=1, dtype=dtype)[:2] if batch is None else \
        _wellcond(8, seed=1, batch=batch, dtype=dtype)[:, :2]
    up = None if batch is None else x_row[..., :1, :].astype(dtype)
    t = ShardTask(server=1, num_servers=4, x_row=x_row,
                  subseed=b"\x07" * 32, style="nserver", attempt=2,
                  u_upstream=up, session_id="abc123")
    t2 = ShardTask.from_bytes(t.to_bytes())
    assert (t2.server, t2.num_servers, t2.style, t2.attempt) == (1, 4, "nserver", 2)
    assert t2.subseed == t.subseed and t2.session_id == "abc123"
    assert t2.x_row.dtype == dtype
    np.testing.assert_array_equal(t2.x_row, x_row)  # bit-exact
    if up is None:
        assert t2.u_upstream is None
    else:
        np.testing.assert_array_equal(t2.u_upstream, up)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("batch", [None, 2])
def test_wire_roundtrip_shard_result(dtype, batch):
    strip = _wellcond(8, seed=2, batch=batch, dtype=dtype)
    strip = strip[..., :2, :]
    r = ShardResult(server=3, l_row=strip, u_row=2 * strip,
                    subseed=b"\x01" * 32, attempt=1, session_id="ff")
    r2 = ShardResult.from_bytes(r.to_bytes())
    assert r2.server == 3 and r2.attempt == 1 and r2.subseed == r.subseed
    assert r2.l_row.dtype == dtype and r2.u_row.dtype == dtype
    np.testing.assert_array_equal(r2.l_row, strip)
    np.testing.assert_array_equal(r2.u_row, 2 * strip)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("batch", [None, 3])
def test_wire_roundtrip_verdict(dtype, batch):
    import jax.numpy as jnp

    a = jnp.asarray(_wellcond(8, seed=3, batch=batch, dtype=dtype))
    l, u, _ = lu_nserver(a, 2)
    u_bad = u.at[..., 3, 3].multiply(1.5)  # force a reject → attribution
    v = authenticate(l, u_bad, a, num_servers=2)
    v2 = Verdict.from_bytes(v.to_bytes())
    assert v2.method == v.method and v2.num_servers == v.num_servers
    for f in ("ok", "residual", "eps", "culprit"):
        got, want = getattr(v2, f), getattr(v, f)
        if isinstance(want, np.ndarray):
            np.testing.assert_array_equal(got, want)
        else:
            assert got == want and type(got) is type(want)
    np.testing.assert_array_equal(v2.server_residual, v.server_residual)
    np.testing.assert_array_equal(v2.server_ok, v.server_ok)
    # accepting verdict: localization fields stay None through the wire
    v_ok = authenticate(l, u, a, num_servers=2)
    v_ok2 = Verdict.from_bytes(v_ok.to_bytes())
    assert v_ok2.server_residual is None and v_ok2.server_ok is None
    assert bool(np.all(v_ok2.ok))


def test_wire_roundtrip_determinant():
    for det in (
        Determinant(sign=-1.0, logabs=1234.56789012345678, dtype="float64"),
        Determinant(sign=1.0, logabs=-0.25, dtype="float32"),
        Determinant(sign=0.0, logabs=float("-inf"), dtype="float64"),
    ):
        d2 = Determinant.from_bytes(det.to_bytes())
        assert d2.sign == det.sign and d2.dtype == det.dtype
        assert d2.logabs == det.logabs  # bit-exact, ±inf included
    assert Determinant.from_bytes(
        Determinant(1.0, float("-inf")).to_bytes()
    ).is_zero()


def test_wire_roundtrip_fault_plan_frame():
    plan = (
        ServerFault(server=1, mode="block", magnitude=0.3),
        ServerFault(server=2, kind="dropout", matrices=(0, 2)),
    )
    f2 = FaultPlanFrame.from_bytes(FaultPlanFrame(plan).to_bytes())
    assert f2.plan == plan


def test_decode_message_dispatches_every_kind():
    t = ShardTask(server=0, num_servers=2,
                  x_row=_wellcond(4)[:2], subseed=b"\x02" * 32)
    r = ShardResult(server=0, l_row=_wellcond(4)[:2],
                    u_row=_wellcond(4)[:2])
    d = Determinant(sign=1.0, logabs=3.5)
    for msg, cls in [(t, ShardTask), (r, ShardResult), (d, Determinant),
                     (FaultPlanFrame(()), FaultPlanFrame)]:
        assert isinstance(decode_message(msg.to_bytes()), cls)


def test_wire_rejects_malformed_frames():
    good = Determinant(sign=1.0, logabs=1.0).to_bytes()
    with pytest.raises(WireError, match="magic"):
        wire.decode(b"JUNK" + good[4:])
    with pytest.raises(WireError):
        wire.decode(good[:10])  # truncated header
    t = ShardTask(server=0, num_servers=2, x_row=_wellcond(4)[:2],
                  subseed=b"\x03" * 32)
    with pytest.raises(WireError):  # truncated array body
        wire.decode(t.to_bytes()[:-16])
    with pytest.raises(WireError, match="expected ShardResult"):
        ShardResult.from_bytes(good)
    with pytest.raises(WireError, match="unknown message kind"):
        decode_message(wire.encode("Nonsense", {}, {}))


def test_wire_rejects_malicious_array_specs():
    """Header fields are attacker-controlled: a negative offset must raise
    WireError, never silently reinterpret header bytes as strip data."""
    import json
    import struct

    def tampered(mutate):
        frame = ShardResult(server=0, l_row=_wellcond(4)[:2],
                            u_row=_wellcond(4)[:2]).to_bytes()
        hlen = struct.unpack_from(">BI", frame, 4)[1]
        header = json.loads(frame[9 : 9 + hlen].decode())
        body = frame[wire._pad(9 + hlen):]
        mutate(header)
        hjson = json.dumps(header, separators=(",", ":")).encode()
        head = wire.MAGIC + struct.pack(">BI", wire.VERSION, len(hjson)) \
            + hjson
        return head.ljust(wire._pad(len(head)), b"\x00") + body

    def set_field(name, value):
        def mutate(header):
            header["arrays"][0][name] = value
        return mutate

    for bad in (set_field("offset", -64), set_field("nbytes", -8),
                set_field("shape", [-2, 4]), set_field("dtype", "O"),
                set_field("offset", "no"), set_field("shape", [3, 5])):
        with pytest.raises(WireError):
            wire.decode(tampered(bad))


# ----------------------------------------------------------- trust boundary
def test_shard_tasks_carry_no_plaintext_or_key_material():
    """The ISSUE's negative test: for every ShardTask of a session, the
    payload contains no verbatim plaintext entry, no blinding-vector
    entry, no Ψ — and does not correlate with the same-position plaintext
    block (the cipher rotated + scaled it away)."""
    from repro.core import keygen

    n = 24
    m = _wellcond(n, seed=11)
    client = SPDCClient()
    session = client.open_session(m, N)
    tasks = session.tasks(check_boundary=True)  # library-side screen
    seed = session.seeds[0]
    key = keygen(client.lambda2, seed, n)
    secrets = np.concatenate([[seed.psi], key.v])

    def informative(a):
        a = np.asarray(a).ravel()
        return a[(a != 0.0) & (np.abs(a) != 1.0)]

    assert len(tasks) == N
    assert {t.server for t in tasks} == set(range(N))
    for t in tasks:
        payload = informative(t.x_row)
        assert np.intersect1d(payload, informative(m)).size == 0
        assert np.intersect1d(payload, secrets).size == 0
        assert t.u_upstream is None  # relay is the transport's job
        assert len(t.subseed) == 32 and t.subseed != seed.digest
        # same-position correlation: the task's strip vs the plaintext's
        # strip at the same rows (padded to n') — rotation + row scaling
        # must have destroyed the alignment
        b = session.block
        rows = slice(t.server * b, min((t.server + 1) * b, n))
        plain = m[rows, :]
        if plain.size:
            crypt = np.asarray(t.x_row)[: plain.shape[0], : n]
            c = np.corrcoef(plain.ravel(), crypt.ravel())[0, 1]
            assert abs(c) < 0.5, f"server {t.server} strip correlates: {c}"


def test_boundary_violation_on_plaintext_payload():
    """If a (buggy) session were about to ship plaintext, tasks() must
    refuse — simulate by splicing the raw matrix into the ciphertext."""
    import jax.numpy as jnp

    n = 16
    m = _wellcond(n, seed=13)
    session = SPDCClient().open_session(m, N)
    session.x_aug = session.x_aug.at[:n, :n].set(jnp.asarray(m))
    with pytest.raises(BoundaryViolation, match="plaintext"):
        session.tasks(check_boundary=True)


# ------------------------------------------------- transport equivalence
@pytest.mark.parametrize("equilibrate", [False, True])
def test_inline_batched_matches_pre_split_fused_sweep(equilibrate):
    """Acceptance: the role split moved equilibrate+augment out of the
    old fused (equilibrate→augment→LU) jit program into the Session's
    PMOP. Both stages are exact in floating point, so the inline path
    must reproduce the pre-split fused program at rtol 1e-10 (observed:
    bit-identical)."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from repro.core.cipher import cipher_batch
    from repro.core.cipher import equilibrate as ced_equilibrate
    from repro.core.augment import augment, padding_for_servers
    from repro.core.decipher import decipher_batch
    from repro.core.keygen import keygen_batch
    from repro.core.seed import seedgen_batch

    B, n = 4, 24
    stack = _wellcond(n, seed=43, batch=B)

    # --- the pre-role-split fused server stage, verbatim ---
    @partial(jax.jit, static_argnames=("num_servers", "padding", "eq"))
    def fused(x, aug_key, *, num_servers, padding, eq):
        if eq:
            x, log2_scale = ced_equilibrate(x)
        else:
            log2_scale = jnp.zeros(x.shape[0], dtype=jnp.int32)
        x_aug = augment(x, padding, key=aug_key)
        l, u, _ = lu_nserver(x_aug, num_servers)
        return l, u, log2_scale

    seeds = seedgen_batch(128, stack)
    v = keygen_batch(128, seeds, n)
    x, metas = cipher_batch(jnp.asarray(stack), v, seeds)
    aug_key = jax.random.key(
        int.from_bytes(seeds[0].digest[8:16], "big") % (2**31)
    )
    l, u, log2_scale = fused(
        x, aug_key, num_servers=N,
        padding=padding_for_servers(n, N), eq=equilibrate,
    )
    want = decipher_batch(seeds, metas, l, u,
                          log2_scale=np.asarray(log2_scale))

    got = outsource_determinant(stack, N, equilibrate=equilibrate)
    assert np.asarray(got.verified).all()
    for i in range(B):
        assert got.dets[i].sign == want[i].sign
        np.testing.assert_allclose(got.dets[i].logabs, want[i].logabs,
                                   rtol=1e-10)

def test_threadpool_matches_inline_every_input_kind():
    m = _wellcond(20, seed=17)
    stack = _wellcond(16, seed=19, batch=3)
    mixed = [m, m[:9, :9], m[:14, :14]]
    with ThreadPoolTransport() as tp:
        for inp in (m, stack, mixed):
            a = outsource_determinant(inp, N)
            b = outsource_determinant(inp, N, transport=tp)
            if hasattr(a, "dets"):
                assert np.asarray(b.verified).all()
                for da, db in zip(a.dets, b.dets):
                    assert da.sign == db.sign
                    np.testing.assert_allclose(db.logabs, da.logabs,
                                               rtol=1e-12)
            else:
                assert b.verified
                assert a.det.sign == b.det.sign
                np.testing.assert_allclose(b.det.logabs, a.det.logabs,
                                           rtol=1e-12)


def test_warm_single_matrix_call_compiles_nothing():
    """A second same-shape single-matrix call is cached dispatches only —
    no trace, lowering or backend compile (the jax.monitoring events the
    benchmark's compile_s_per_answer sums) — and the same det, bit for
    bit."""
    import threading

    import jax

    compile_events = {
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    }
    m = _wellcond(32, seed=59)
    first = outsource_determinant(m, N)
    me, seen = threading.get_ident(), []

    def on_event(event, duration, **_):
        if event in compile_events and threading.get_ident() == me:
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        second = outsource_determinant(m, N)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    assert seen == []
    assert second.verified
    assert (second.det.sign, second.det.logabs) == (first.det.sign,
                                                    first.det.logabs)


def test_session_roles_drive_manually():
    """The role API without the facade: client opens a session, an
    EdgeServer farm executes the relay task by task, the session collects
    ShardResults — same determinant as the one-call facade."""
    n = 20
    m = _wellcond(n, seed=23)
    client = SPDCClient(method="q2")
    session = client.open_session(m, N)
    edges = [EdgeServer(i) for i in range(N)]
    results, u_rows = [], []
    for task in session.tasks():
        if task.server > 0:
            task = task.with_upstream(np.concatenate(u_rows, axis=-2))
        res = edges[task.server].run(task)
        # round-trip every message through the wire, as a real remote
        # worker would see it
        res = ShardResult.from_bytes(res.to_bytes())
        results.append(res)
        u_rows.append(np.asarray(res.u_row))
    out = session.collect(results)
    ref = outsource_determinant(m, N, method="q2")
    assert out.verified
    assert out.det.sign == ref.det.sign
    np.testing.assert_allclose(out.det.logabs, ref.det.logabs, rtol=1e-12)


def test_threadpool_recovery_emits_fresh_shard_tasks():
    """Recovery over a message transport: the session re-issues ShardTasks
    with fresh sub-seeds; the healed det matches honest at rtol 1e-10."""
    from repro.distrib.recovery import dispatch_subseed

    m = _wellcond(16, seed=29)
    honest = outsource_determinant(m, N)
    res = outsource_determinant(
        m, N, method="q2", faults=ServerFault(server=1, mode="block"),
        recover=True, standby=1, transport="threadpool",
    )
    assert res.verified and res.report.recovery.ok
    assert 1 in res.report.recovery.servers_replaced
    # in-band poisoning: the relay forwarded the tampered row, so healing
    # cascades one row per round (DESIGN.md §4.3)
    assert 2 <= res.report.recovery.rounds <= N
    np.testing.assert_allclose(res.det.logabs, honest.det.logabs,
                               rtol=1e-10)
    # every event's sub-seed is the documented derivation — fresh per
    # (server, attempt), never the raw digest
    seen = set()
    for e in res.report.recovery.events:
        assert e.subseed not in seen
        seen.add(e.subseed)


def test_resolve_transport_rules():
    assert resolve_transport(None).name == "inline"
    assert resolve_transport(None, distributed=True).name == "shardmap"
    assert resolve_transport("threadpool").name == "threadpool"
    inst = InlineTransport()
    assert resolve_transport(inst) is inst
    with pytest.raises(ValueError, match="unknown transport"):
        resolve_transport("carrier-pigeon")
    with pytest.raises(ValueError, match="conflicts"):
        resolve_transport("threadpool", distributed=True)
    with pytest.raises(ValueError, match="conflicts"):
        resolve_transport(inst, distributed=True)


def test_spawning_transports_refuse_an_accelerator(monkeypatch):
    """Under an accelerator the multiprocess and self-hosted socket
    transports raise AcceleratorHeld at construction: each spawned worker
    would import JAX and wait on the chip this process holds. A socket
    client of remote daemons spawns nothing and stays allowed."""
    import repro.runtime
    from repro.api import SocketTransport

    monkeypatch.setattr(repro.runtime, "on_cpu", lambda: False)
    with pytest.raises(AcceleratorHeld, match="multiprocess"):
        MultiprocessTransport()
    with pytest.raises(AcceleratorHeld, match="self-hosted socket"):
        SocketTransport()
    assert issubclass(AcceleratorHeld, TransportError)
    SocketTransport(addresses=("tcp://127.0.0.1:9",)).close()


def test_transport_config_rules():
    """Satellite: the declarative third leg of resolve_transport —
    frozen/hashable, validated at construction, shared when resolved,
    fresh when built."""
    from repro.api import TransportConfig

    cfg = TransportConfig("threadpool", max_workers=2)
    assert hash(cfg) == hash(TransportConfig("threadpool", max_workers=2))
    shared = resolve_transport(cfg)
    assert shared is resolve_transport(TransportConfig("threadpool",
                                                       max_workers=2))
    owned = cfg.build()
    try:
        assert owned is not shared and owned.name == "threadpool"
    finally:
        owned.close()
    # a closed shared instance is rebuilt on the next resolve
    shared.close()
    rebuilt = resolve_transport(cfg)
    assert rebuilt is not shared and not rebuilt.closed
    # field applicability is validated up front, not at build time
    with pytest.raises(ValueError, match="unknown transport"):
        TransportConfig("carrier-pigeon")
    with pytest.raises(ValueError, match="addresses"):
        TransportConfig("inline", addresses=("tcp://h:1",))
    with pytest.raises(ValueError, match="max_workers"):
        TransportConfig("socket", max_workers=3)
    with pytest.raises(ValueError, match="program"):
        TransportConfig("threadpool", program="baseline")
    with pytest.raises(ValueError, match="timeout"):
        TransportConfig("inline", timeout=5.0)
    # list addresses are coerced so the config stays hashable
    assert TransportConfig(
        "socket", addresses=["unix:///a"]
    ).addresses == ("unix:///a",)


def test_transport_lifecycle_uniform():
    """Satellite: every transport is a context manager; close() is
    idempotent, flips `closed`, and a closed transport refuses
    dispatch with a typed error."""
    from repro.api.transport import _FACTORIES

    for name in ("inline", "shardmap", "threadpool", "multiprocess",
                 "socket"):
        assert name in _FACTORIES
    for make in (InlineTransport, ThreadPoolTransport):
        with make() as t:
            assert not t.closed
        assert t.closed
        t.close()  # idempotent
        with pytest.raises(TransportError, match="closed"):
            t.factor([])
        with pytest.raises(TransportError, match="closed"):
            t.driver_submit(lambda: None)


def test_client_owns_config_transport_not_instances():
    """Satellite: SPDCClient builds-and-OWNS a TransportConfig transport
    (context manager closes it); a passed instance stays caller-owned."""
    from repro.api import TransportConfig

    with SPDCClient(transport=TransportConfig("threadpool")) as client:
        inner = client.transport
        assert isinstance(inner, ThreadPoolTransport)
        assert client.open_session(_wellcond(12, seed=63), 2).run().verified
    assert inner.closed
    mine = ThreadPoolTransport()
    try:
        with SPDCClient(transport=mine) as client:
            assert client.transport is mine
        assert not mine.closed  # caller-owned: the client must not close it
    finally:
        mine.close()


# ------------------------------------------------- report consolidation
def test_report_consolidation_and_deprecated_shims():
    """Satellite: verdict/recovery/fleet/timings live on ONE typed
    `result.report`; the old top-level attributes still answer but warn
    (pytest.ini escalates those warnings to errors inside repro/tests,
    so no internal caller can quietly keep using them)."""
    res = outsource_determinant(_wellcond(12, seed=65), 2)
    rep = res.report
    assert bool(np.all(rep.verdict.ok)) and rep.recovery is None
    assert rep.fleet is None
    t = rep.timings
    assert t.pmop_s > 0 and t.collect_s > 0
    assert t.total_s == pytest.approx(t.pmop_s + t.dispatch_s + t.collect_s)
    for name in ("verdict", "recovery", "fleet"):
        with pytest.warns(DeprecationWarning, match=f"report.{name}"):
            assert getattr(res, name) is getattr(rep, name)


def test_run_pipelined_overlaps_and_preserves_order():
    """Tentpole: the async-overlap pipeline — up to `depth` sessions in
    flight, batch k+1's PMOP hidden under batch k's wire time, results
    in input order."""
    mats = [_wellcond(12 + 2 * i, seed=70 + i) for i in range(5)]
    client = SPDCClient()
    with ThreadPoolTransport() as tp:
        outs = client.run_pipelined(mats, 2, depth=3, transport=tp)
    assert len(outs) == len(mats)
    for m, r in zip(mats, outs):
        ws, wl = np.linalg.slogdet(m)
        assert r.verified and r.det.sign == ws
        np.testing.assert_allclose(r.det.logabs, wl, rtol=1e-10)
        assert r.report.timings.dispatch_s > 0
    with pytest.raises(ValueError, match="depth"):
        client.run_pipelined(mats, 2, depth=0)


def test_session_start_matches_run_on_inline():
    """start() on a fused transport completes synchronously and collects
    to the same result as run() — same verdict, same det."""
    m = _wellcond(16, seed=67)
    client = SPDCClient()
    pending = client.open_session(m, 2).start()
    assert pending.done()
    a = pending.result()
    b = client.open_session(m, 2).run()
    assert a.verified and b.verified
    assert a.det.sign == b.det.sign
    np.testing.assert_allclose(a.det.logabs, b.det.logabs, rtol=1e-12)


def test_edge_server_requires_relay_rows():
    t = ShardTask(server=1, num_servers=2, x_row=_wellcond(8)[:4],
                  subseed=b"\x04" * 32)
    with pytest.raises(ValueError, match="upstream"):
        EdgeServer().run(t)


# ----------------------------------------------------- config reflection
def test_spdc_config_protocol_kwargs_match_signature():
    """Satellite: protocol_kwargs() must emit only (and exercise all of)
    the real outsource_determinant keywords it models — the reflection
    guard that stops the config from drifting again."""
    from repro.configs import SPDCConfig

    params = set(
        inspect.signature(outsource_determinant).parameters
    )
    kwargs = SPDCConfig().protocol_kwargs()
    assert set(kwargs) <= params, set(kwargs) - params
    # the config must model every protocol kwarg except the per-call ones
    per_call = {"m", "num_servers", "use_kernel", "distributed",
                "faithful_sign", "tamper", "faults"}
    assert set(kwargs) == params - per_call


def test_bucket_key_protocol_kwargs_match_mixed_signature():
    from repro.core.protocol import outsource_determinant_mixed
    from repro.serve import BucketKey

    params = set(
        inspect.signature(outsource_determinant_mixed).parameters
    )
    kwargs = BucketKey(pad_to=64, num_servers=4).protocol_kwargs()
    assert set(kwargs) <= params, set(kwargs) - params


# --------------------------------------------------- gateway over transports
def test_gateway_threadpool_transport():
    from repro.configs import SPDCConfig, SPDCGatewayConfig
    from repro.serve import SPDCGateway

    cfg = SPDCGatewayConfig(
        name="gw-tp-test", buckets=(16,), max_batch=4, pad_batches=False,
        spdc=SPDCConfig(num_servers=2, transport="threadpool"),
    )
    gw = SPDCGateway(cfg)
    mats = [_wellcond(k, seed=100 + k) for k in (8, 12, 16, 10)]
    rids = [gw.submit(m) for m in mats]
    gw.drain()
    for rid, m in zip(rids, mats):
        r = gw.take(rid)
        assert r is not None and r.verified
        ws, wl = np.linalg.slogdet(m)
        assert r.det.sign == ws
        np.testing.assert_allclose(r.det.logabs, wl, rtol=1e-10)


# -------------------------------------------- multiprocess acceptance (CI)
@pytest.fixture(scope="module")
def mp_transport():
    t = MultiprocessTransport()
    yield t
    t.close()


def test_multiprocess_honest_end_to_end(mp_transport):
    """N=4 real worker processes; every message crosses the boundary as
    wire-codec bytes over an OS pipe; det matches numpy at rtol 1e-10."""
    n = 16
    m = _wellcond(n, seed=31)
    res = outsource_determinant(m, N, transport=mp_transport)
    assert len(mp_transport.workers) == N  # genuinely 4 processes
    ws, wl = np.linalg.slogdet(m)
    assert res.verified and res.det.sign == ws
    np.testing.assert_allclose(res.det.logabs, wl, rtol=1e-10)


@pytest.mark.parametrize("method", ["q2", "q3"])
def test_multiprocess_acceptance_tamper_recovery(mp_transport, method):
    """THE acceptance criterion: 4 worker processes, worker 1 tampers its
    strip (in-band — downstream workers consume the poisoned relay), the
    client localizes it and heals via re-dispatched ShardTasks; the final
    verdict passes under Q2 and Q3 and the det matches the honest run at
    rtol 1e-10."""
    n = 16
    m = _wellcond(n, seed=37)
    honest = outsource_determinant(m, N)
    res = outsource_determinant(
        m, N, method=method,
        faults=ServerFault(server=1, mode="block", magnitude=0.3),
        recover=True, standby=1, transport=mp_transport,
    )
    assert res.verified and res.report.recovery.ok
    assert res.report.recovery.events[0].server == 1  # localized the culprit
    assert 1 in res.report.recovery.servers_replaced
    assert res.det.sign == honest.det.sign
    np.testing.assert_allclose(res.det.logabs, honest.det.logabs,
                               rtol=1e-10)
    ws, wl = np.linalg.slogdet(m)
    assert res.det.sign == ws
    np.testing.assert_allclose(res.det.logabs, wl, rtol=1e-10)


def test_multiprocess_batched_sweep(mp_transport):
    stack = _wellcond(16, seed=41, batch=2)
    res = outsource_determinant(stack, N, transport=mp_transport)
    assert np.asarray(res.verified).all()
    for i in range(2):
        ws, wl = np.linalg.slogdet(stack[i])
        assert res.dets[i].sign == ws
        np.testing.assert_allclose(res.dets[i].logabs, wl, rtol=1e-10)


def test_multiprocess_timeout_is_typed_and_worker_respawns(mp_transport):
    """A worker sleeping past the per-request deadline surfaces a TYPED
    TransportTimeout (a TransportError — callers catching the base class
    keep working), the stuck process is killed, and the next dispatch to
    that worker id transparently respawns it."""
    import time

    m = _wellcond(16, seed=43)
    session = SPDCClient().open_session(m, N)
    task = session.tasks()[0]
    slow = ServerFault(server=0, kind="delay", delay_s=30.0)
    pid_before = mp_transport._conn(0) and mp_transport._procs[0].pid
    t0 = time.monotonic()
    # start() is the nonblocking half of the redesigned dispatch surface:
    # it hands back a Future immediately; result() surfaces the typed error
    fut = mp_transport.start(task, 0, faults=(slow,), timeout=0.5)
    with pytest.raises(TransportTimeout, match="request deadline"):
        mp_transport.result(fut, timeout=60)
    assert time.monotonic() - t0 < 20.0  # did NOT wait out the sleep
    assert issubclass(TransportTimeout, TransportError)
    assert 0 not in mp_transport.workers  # killed and discarded
    res = mp_transport.submit(task, 0)  # blocking facade over start/result
    assert res.server == 0  # respawned on demand and served
    assert mp_transport._procs[0].pid != pid_before


def test_multiprocess_worker_killed_mid_session_heals(mp_transport):
    """Regression: SIGKILL a live worker, then run a full session through
    the same transport — the dead worker is detected (TransportWorkerDied
    under the hood), respawned, and the protocol completes verified."""
    import os
    import signal
    import time

    m = _wellcond(16, seed=47)
    res = outsource_determinant(m, N, transport=mp_transport)
    assert res.verified  # all four workers warm and live
    victim = mp_transport._procs[1]
    os.kill(victim.pid, signal.SIGKILL)
    victim.join(timeout=10)
    time.sleep(0.1)
    res2 = outsource_determinant(m, N, transport=mp_transport)
    assert res2.verified
    assert mp_transport._procs[1].pid != victim.pid  # genuinely respawned
    ws, wl = np.linalg.slogdet(m)
    assert res2.det.sign == ws
    np.testing.assert_allclose(res2.det.logabs, wl, rtol=1e-10)


def test_multiprocess_rateless_streams_through_worker_processes():
    """Rateless dispatch over REAL worker processes: per-request timeouts
    cut a sleeping worker loose mid-session, the strip re-streams to a
    live sibling, and the fleet report attributes the slowness."""
    from repro.configs import RatelessConfig

    m = _wellcond(16, seed=53)
    cfg = RatelessConfig(request_timeout_s=1.0, probation_cooldown_s=60.0)
    client = SPDCClient(rateless=cfg, recover=True)
    fault = ServerFault(server=1, kind="delay", delay_s=8.0)
    with MultiprocessTransport() as t:
        out = client.open_session(m, N, faults=fault).run(t)
    assert out.verified
    assert out.report.fleet.timeouts >= 1
    w1 = out.report.fleet.workers[1]
    assert w1["failures"] >= 1 and w1["completed"] == 0
    ws, wl = np.linalg.slogdet(m)
    np.testing.assert_allclose(out.det.logabs, wl, rtol=1e-8)
