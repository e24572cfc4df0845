"""Verification-driven recovery: localize → re-dispatch one shard → splice.

Includes the acceptance end-to-end: with N=4 servers and ANY single server
tampering or dropping out, the recovery scheduler localizes the fault,
re-dispatches only that shard, and the final determinant passes Q2 AND Q3
and matches the honest-run value at rtol=1e-10 (f64) — for single matrices
and (B, n, n) batches.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    ServerFault, augment_for_servers, authenticate, lu_block_row, lu_nserver,
    outsource_determinant,
)
from repro.distrib.recovery import (
    RecoveryReport, ServerPool, dispatch_subseed, recover_lu,
    recovery_comm_elements, rederive_shard,
)

N = 4


def _wellcond(n, seed=0, batch=None):
    rng = np.random.default_rng(seed)
    if batch is None:
        return rng.standard_normal((n, n)) + n * np.eye(n)
    return rng.standard_normal((batch, n, n)) + n * np.eye(n)


SINGLE_SERVER_FAULTS = [
    ServerFault(server=s, kind=kind, mode=mode, target=target)
    for s in range(N)
    for kind, mode, target in [
        ("tamper", "single", "u"),
        ("tamper", "sign_flip", "l"),
        ("tamper", "block", "lu"),
        ("dropout", "single", "u"),
    ]
]


# ------------------------------------------------------------- acceptance
@pytest.mark.parametrize(
    "fault", SINGLE_SERVER_FAULTS,
    ids=[f"s{f.server}-{f.kind}-{f.mode}-{f.target}"
         for f in SINGLE_SERVER_FAULTS],
)
def test_recovery_end_to_end_single_matrix(fault):
    """Acceptance: any single server tampering/dropping out → localized,
    ONE shard re-dispatched, Q2+Q3 pass, det == honest at rtol 1e-10."""
    n = 32
    m = _wellcond(n, seed=fault.server + 7)
    honest = outsource_determinant(m, N)
    res = outsource_determinant(m, N, faults=fault, recover=True, standby=1)

    assert res.verified
    rep = res.report.recovery
    assert isinstance(rep, RecoveryReport) and rep.ok
    # report-level fault: exactly one round, only the culprit's shard moved
    assert rep.rounds == 1
    assert rep.servers_replaced == (fault.server,)
    assert rep.standby_used == 1
    assert rep.events[0].replacement == N  # the provisioned standby

    # the HEALED factors pass BOTH Q2 and Q3 (not just the protocol's
    # configured method) — exercised on the raw recovery scheduler
    x_aug, _ = _reconstruct_ciphertext(res, m)
    lf, uf, _ = lu_nserver(x_aug, N, faults=(fault,))
    l2, u2, _, rep2 = recover_lu(lf, uf, x_aug, num_servers=N, standby=1)
    assert rep2.ok
    for method in ("q2", "q3"):
        v = authenticate(l2, u2, x_aug, num_servers=N, method=method)
        assert v.ok, (method, v.residual)

    assert res.report.verdict.ok and res.report.verdict.method == "q3"
    assert res.det.sign == honest.det.sign
    np.testing.assert_allclose(res.det.logabs, honest.det.logabs, rtol=1e-10)
    want_s, want_la = np.linalg.slogdet(m)
    assert res.det.sign == want_s
    np.testing.assert_allclose(res.det.logabs, want_la, rtol=1e-10)


def _reconstruct_ciphertext(res, m):
    """Replay the client's PMOP to rebuild x_aug for out-of-band checks."""
    from repro.core import augment, cipher, keygen

    key = keygen(128, res.seed, m.shape[-1])
    x, _ = cipher(jnp.asarray(m, dtype=jnp.float64), key, res.seed)
    aug_key = jax.random.key(
        int.from_bytes(res.seed.digest[8:16], "big") % (2**31)
    )
    return augment(x, res.padding, key=aug_key), key


@pytest.mark.parametrize("kind", ["tamper", "dropout"])
def test_recovery_end_to_end_batched(kind):
    """Acceptance (batch leg): per-matrix faults across different servers
    all heal in one pass; every det matches honest at rtol 1e-10."""
    B, n = 5, 32
    m = _wellcond(n, seed=11, batch=B)
    honest = outsource_determinant(m, N)
    plan = (
        ServerFault(server=1, kind=kind, matrices=(0,)),
        ServerFault(server=3, kind=kind, matrices=(2, 4)),
    )
    res = outsource_determinant(m, N, faults=plan, recover=True, standby=2)
    assert res.verified.all()
    assert res.report.recovery.ok
    assert res.report.recovery.servers_replaced == (1, 3)
    spliced = {e.server: e.matrices for e in res.report.recovery.events}
    assert spliced[1] == (0,) and spliced[3] == (2, 4)
    # the healed batch passes Q2 as well as the default Q3
    res_q2 = outsource_determinant(
        m, N, method="q2", faults=plan, recover=True, standby=2
    )
    assert res_q2.verified.all() and res_q2.report.recovery.ok
    for i in range(B):
        assert res.dets[i].sign == honest.dets[i].sign
        np.testing.assert_allclose(
            res.dets[i].logabs, honest.dets[i].logabs, rtol=1e-10
        )


def test_recovery_distributed_pipeline():
    """Faults injected on the shard_map pipeline heal the same way.

    The first re-dispatch must target the genuinely faulty server; the
    loop may then heal a downstream row whose splice-induced rounding
    grazes ε(N) (a replacement server cannot be bitwise-identical to the
    jitted pipeline), but it must converge within the round budget.
    """
    n = 32
    m = _wellcond(n, seed=13)
    honest = outsource_determinant(m, N)
    res = outsource_determinant(
        m, N, distributed=True,
        faults=ServerFault(server=2, kind="dropout"),
        recover=True, standby=1,
    )
    assert res.verified and res.report.recovery.ok
    assert res.report.recovery.events[0].server == 2
    assert res.report.recovery.rounds <= N
    np.testing.assert_allclose(res.det.logabs, honest.det.logabs, rtol=1e-10)


@pytest.mark.parametrize("mode,target", [("block", "lu"), ("single", "u")])
def test_recovery_heals_a_server_on_a_shared_chip(mode, target):
    """Server 5 of 16 on a 4-chip mesh (slot 1 of chip 1, k = 4) tampers:
    Q3 localizes it, and the "pipeline" recompute heals its strip alone.
    The spliced strip is that recompute bit for bit, and the honest
    relay's strip to rounding (XLA:CPU rounds the relay's products in
    another order than a recompute outside it, at k = 1 as at k = 4)."""
    from repro.distrib.recovery import lu_block_row_jit
    from repro.distrib.spdc_pipeline import lu_nserver_shardmap
    from repro.launch.mesh import make_smoke_mesh

    n, servers, culprit = 128, 16, 5
    x = jnp.asarray(_wellcond(n, seed=59))
    mesh = make_smoke_mesh((4,), ("servers",))
    l, u = lu_nserver_shardmap(x, servers, mesh=mesh)
    fault = ServerFault(server=culprit, mode=mode, target=target)
    lf, uf = lu_nserver_shardmap(x, servers, mesh=mesh, faults=(fault,))
    b = n // servers
    rows = slice(culprit * b, (culprit + 1) * b)
    bad = np.any(np.asarray(uf) != np.asarray(u), axis=1)
    assert bad.any() and not bad[: rows.start].any() and not bad[rows.stop:].any()

    lh, uh, verdict, report = recover_lu(
        lf, uf, x, num_servers=servers, method="q3", standby=1,
        style="pipeline",
    )
    assert bool(np.all(verdict.ok)) and report.ok
    assert report.events[0].server == culprit
    assert report.servers_replaced == (culprit,)
    lr, ur = lu_block_row_jit(x, uf, culprit, servers, style="pipeline")
    np.testing.assert_array_equal(np.asarray(lh[rows]), np.asarray(lr))
    np.testing.assert_array_equal(np.asarray(uh[rows]), np.asarray(ur))
    np.testing.assert_allclose(np.asarray(uh), np.asarray(u), atol=1e-12)
    np.testing.assert_allclose(np.asarray(lh), np.asarray(l), atol=1e-12)


def test_recovery_through_the_shardmap_transport_on_shared_chips():
    """outsource_determinant(M, 16, transport="shardmap") on 8 devices (two
    servers per chip) with server 5 dropping out heals to the honest
    determinant."""
    n = 64
    m = _wellcond(n, seed=61)
    honest = outsource_determinant(m, 16)
    res = outsource_determinant(
        m, 16, transport="shardmap",
        faults=ServerFault(server=5, kind="dropout"),
        recover=True, standby=1,
    )
    assert res.verified and res.report.recovery.ok
    assert res.report.recovery.events[0].server == 5
    np.testing.assert_allclose(res.det.logabs, honest.det.logabs, rtol=1e-10)


def test_recovery_in_band_cascade():
    """Relay poisoning: the tampered U row was consumed downstream, so the
    scheduler heals one block row per round — and still converges to the
    honest determinant."""
    n = 32
    m = _wellcond(n, seed=17)
    honest = outsource_determinant(m, N)
    fault = ServerFault(server=1, in_band=True, mode="block", magnitude=0.3)
    res = outsource_determinant(m, N, faults=fault, recover=True, standby=N)
    assert res.verified and res.report.recovery.ok
    assert res.report.recovery.rounds >= 2  # genuinely cascaded
    assert res.report.recovery.rounds <= N
    assert 1 in res.report.recovery.servers_replaced
    np.testing.assert_allclose(res.det.logabs, honest.det.logabs, rtol=1e-10)


def test_recovery_straggler_redispatch():
    """A server slower than the deadline is treated as dropped and its
    shard re-dispatched; within the deadline the client just waits."""
    n = 32
    m = _wellcond(n, seed=19)
    fault = ServerFault(server=2, kind="delay", delay_rounds=6)
    late = outsource_determinant(
        m, N, faults=fault, straggler_deadline=3, recover=True, standby=1
    )
    assert late.verified and late.report.recovery.servers_replaced == (2,)
    ontime = outsource_determinant(m, N, faults=fault, straggler_deadline=10)
    assert ontime.verified and ontime.report.recovery is None


def test_recovery_without_standby_uses_healthy_neighbor():
    n = 32
    m = _wellcond(n, seed=23)
    res = outsource_determinant(
        m, N, faults=ServerFault(server=1), recover=True, standby=0
    )
    assert res.verified
    assert res.report.recovery.standby_used == 0
    assert res.report.recovery.events[0].replacement == 2  # culprit's neighbor


def test_recovery_cost_is_one_shard_not_full_restart():
    """The wire cost of every recovery event is << one full re-outsource
    (n² ciphertext resend) — the 'one extra hop' property."""
    n = 64
    m = _wellcond(n, seed=29)
    res = outsource_determinant(
        m, N, faults=ServerFault(server=0), recover=True, standby=1
    )
    full_restart = n * n
    for e in res.report.recovery.events:
        assert e.comm_elements < full_restart
    assert recovery_comm_elements(n, N, 0) == 3 * (n // N) * n


# ------------------------------------------------------------- unit pieces
def test_lu_block_row_matches_honest_rows():
    n = 24
    a = jnp.asarray(_wellcond(n, seed=31))
    l, u, _ = lu_nserver(a, N)
    b = n // N
    for s in range(N):
        lr, ur = lu_block_row(a, u, s, N)
        np.testing.assert_allclose(
            np.asarray(lr), np.asarray(l[s * b : (s + 1) * b]), atol=1e-10
        )
        np.testing.assert_allclose(
            np.asarray(ur), np.asarray(u[s * b : (s + 1) * b]), atol=1e-10
        )


@pytest.mark.parametrize("batch", [None, 3], ids=["single", "stack"])
def test_jitted_recompute_bit_matches_jitted_sweep(batch):
    """The splice contract at either rank: the jitted strip recompute
    replays the jitted inline sweep bit for bit, the culprit's strip
    included (b = 64 takes the blocked panel)."""
    from repro.api.transport import _lu_sweep
    from repro.distrib.recovery import lu_block_row_jit

    n, culprit = 256, 2
    x = jnp.asarray(_wellcond(n, seed=53, batch=batch), dtype=jnp.float32)
    plan = (ServerFault(server=culprit, mode="block", target="lu"),)
    l, u = _lu_sweep(x, num_servers=N)
    _, uf = _lu_sweep(x, num_servers=N, faults=plan)
    b = n // N
    rows = slice(culprit * b, (culprit + 1) * b)
    assert not np.array_equal(np.asarray(uf[..., rows, :]),
                              np.asarray(u[..., rows, :]))
    for s in range(culprit + 1):
        lr, ur = lu_block_row_jit(x, uf, s, N)
        rows = slice(s * b, (s + 1) * b)
        np.testing.assert_array_equal(np.asarray(lr),
                                      np.asarray(l[..., rows, :]))
        np.testing.assert_array_equal(np.asarray(ur),
                                      np.asarray(u[..., rows, :]))


def test_lu_block_row_ignores_corrupted_own_and_downstream_rows():
    """The recompute must be a function of x and the rows ABOVE only."""
    n = 24
    a = jnp.asarray(_wellcond(n, seed=37))
    l, u, _ = lu_nserver(a, N)
    b = n // N
    u_bad = u.at[2 * b :, :].set(999.0)  # garbage at and below server 2
    lr, ur = lu_block_row(a, u_bad, 2, N)
    np.testing.assert_allclose(
        np.asarray(ur), np.asarray(u[2 * b : 3 * b]), atol=1e-10
    )


def test_recover_lu_direct_api():
    n = 24
    a = jnp.asarray(_wellcond(n, seed=41))
    l, u, _ = lu_nserver(
        a, N, faults=(ServerFault(server=3, kind="dropout"),)
    )
    l2, u2, verdict, report = recover_lu(
        l, u, a, num_servers=N, standby=1, digest=b"t"
    )
    assert verdict.ok and report.ok and report.servers_replaced == (3,)
    np.testing.assert_allclose(np.asarray(l2 @ u2), np.asarray(a), atol=1e-8)


def test_server_pool_standby_then_neighbor():
    pool = ServerPool(num_servers=4, standby=2)
    p1, pool = pool.replacement_for(1)
    assert p1 == 4
    p2, pool = pool.replacement_for(2)
    assert p2 == 5 and pool.spares_used == 2
    p3, pool = pool.replacement_for(3)  # spares exhausted → healthy neighbor
    assert p3 == 0
    assert pool.retired == (1, 2, 3)


def test_server_pool_standby_exhaustion_batched():
    """Batched sweep with MORE culprits than spares: the pool hands out
    both standbys, then falls back to healthy neighbors — every matrix
    still heals to the honest determinant, and every re-dispatch carries
    a fresh sub-seed."""
    B, n = 4, 32
    m = _wellcond(n, seed=61, batch=B)
    honest = outsource_determinant(m, N)
    plan = (
        ServerFault(server=0, kind="tamper", matrices=(0,)),
        ServerFault(server=1, kind="dropout", matrices=(1,)),
        ServerFault(server=2, kind="tamper", mode="sign_flip",
                    matrices=(2,)),
        ServerFault(server=3, kind="dropout", matrices=(3,)),
    )
    res = outsource_determinant(m, N, faults=plan, recover=True, standby=2)
    assert np.asarray(res.verified).all()
    rep = res.report.recovery
    assert rep.ok and rep.standby_used == 2  # spares genuinely exhausted
    assert rep.servers_replaced == (0, 1, 2, 3)
    repl = [e.replacement for e in rep.events]
    assert repl[:2] == [N, N + 1]  # the provisioned standbys, in order
    assert all(r < N for r in repl[2:])  # then healthy-neighbor fallback
    for e in rep.events:
        assert e.replacement != e.server
    subseeds = [e.subseed for e in rep.events]
    assert len(set(subseeds)) == len(subseeds)
    for i in range(B):
        assert res.dets[i].sign == honest.dets[i].sign
        np.testing.assert_allclose(
            res.dets[i].logabs, honest.dets[i].logabs, rtol=1e-10
        )


def test_standby_exhaustion_cascade_fresh_subseed_per_attempt():
    """An in-band cascade with ONE spare: after the spare is spent the
    remaining rounds ride neighbors, and the sub-seed is fresh on every
    event — re-dispatches of different rounds never share a channel key."""
    n = 32
    m = _wellcond(n, seed=67)
    honest = outsource_determinant(m, N)
    fault = ServerFault(server=1, in_band=True, mode="block", magnitude=0.3)
    res = outsource_determinant(m, N, faults=fault, recover=True, standby=1)
    assert res.verified and res.report.recovery.ok
    assert res.report.recovery.rounds >= 2  # genuinely cascaded past the spare
    assert res.report.recovery.standby_used == 1
    repl = [e.replacement for e in res.report.recovery.events]
    assert repl[0] == N and any(r < N for r in repl[1:])
    subseeds = [e.subseed for e in res.report.recovery.events]
    assert len(set(subseeds)) == len(subseeds)
    np.testing.assert_allclose(res.det.logabs, honest.det.logabs, rtol=1e-10)


def test_dispatch_subseed_is_fresh_per_attempt():
    d = b"\x01" * 32
    s1 = dispatch_subseed(d, 2, 1)
    s2 = dispatch_subseed(d, 2, 2)
    s3 = dispatch_subseed(d, 3, 1)
    assert len({s1, s2, s3}) == 3


def test_rederive_shard_matches_full_augmentation():
    rng = np.random.default_rng(43)
    x = jnp.asarray(rng.standard_normal((10, 10)))
    key = jax.random.key(5)
    x_aug, p = augment_for_servers(x, N, key=key)
    b = x_aug.shape[-1] // N
    for s in range(N):
        shard = rederive_shard(x, padding=p, server=s, num_servers=N,
                               aug_key=key)
        np.testing.assert_array_equal(
            np.asarray(shard), np.asarray(x_aug[s * b : (s + 1) * b])
        )


def test_hardened_config_profile_drives_recovery():
    """SPDC_EDGE_HARDENED's standby/recover/straggler fields map onto the
    protocol signature (protocol_kwargs keeps them from drifting)."""
    from repro.configs import SPDC_EDGE_HARDENED as cfg

    assert cfg.recover and cfg.standby == 2
    m = _wellcond(32, seed=53)
    res = outsource_determinant(
        m, N, faults=ServerFault(server=1), **cfg.protocol_kwargs()
    )
    assert res.verified and res.report.recovery.ok
    assert res.report.recovery.events[0].replacement == N  # healed on a standby


def test_server_pool_never_returns_culprit_when_avoidable():
    """Spares and fresh neighbors exhausted → a retired-but-healed server
    gets the shard, never the culprit itself (N=2 worst case)."""
    pool = ServerPool(num_servers=2, standby=0)
    p0, pool = pool.replacement_for(0)
    assert p0 == 1
    p1, pool = pool.replacement_for(1)
    assert p1 == 0  # retired-but-healed, NOT the culprit


def test_recover_lu_stops_once_verdict_accepts():
    """Matrices whose verdict already passes are never re-dispatched: a
    clean factorization with a pre-computed verdict exits in zero rounds."""
    n = 24
    a = jnp.asarray(_wellcond(n, seed=59))
    l, u, _ = lu_nserver(a, N)
    v0 = authenticate(l, u, a, num_servers=N)
    l2, u2, v, rep = recover_lu(
        l, u, a, num_servers=N, standby=1, verdict=v0
    )
    assert rep.ok and rep.rounds == 0 and rep.events == []
    assert l2 is l and u2 is u


def test_unrecoverable_without_recover_flag():
    """Default behavior unchanged: no recover → rejected verdict stands."""
    n = 24
    m = _wellcond(n, seed=47)
    res = outsource_determinant(m, N, faults=ServerFault(server=1))
    assert not res.verified
    assert res.report.recovery is None
    assert res.report.verdict.culprit == 1
