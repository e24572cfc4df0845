"""repro.linalg — the shared-LU op plan and the differentiable ops.

Tier for DESIGN.md §12: `LinalgSession` (slogdet/solve/inv on ONE
verified outsourced factorization), the `secure_*` custom-VJP ops, the
TriSolve wire layer, the trust-boundary invariants (blinding, secret
probe lanes), and tamper/heal through the recovery machinery.

Runs on both CI legs: with JAX_ENABLE_X64=0 everything executes in f32
(tolerances widen with the dtype); tests comparing against the protocol's
f64-calibrated gradients carry `needs_x64`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api.messages import TriSolveResult, TriSolveTask
from repro.api.transport import InlineTransport, ThreadPoolTransport
from repro.core.faults import ServerFault
from repro.linalg import (
    LinalgSession,
    LinalgVerificationError,
    SecureLinalg,
    blind_rhs,
    outsource_solve,
    secure_inv,
    secure_slogdet,
    secure_solve,
)

X64 = bool(jax.config.jax_enable_x64)
needs_x64 = pytest.mark.skipif(
    not X64, reason="gradient bar calibrated against float64 protocol runs"
)

#: op-plan acceptance vs numpy references, by compute dtype
TOL = 1e-9 if X64 else 2e-3
N_SERVERS = 2


def _wellcond(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, n)) + n * np.eye(n)


def _spd(n, seed=0, cond=50.0):
    """RBF-like SPD matrix — the GP workload's shape (near-worst no-pivot
    input when growth_safe is off)."""
    rng = np.random.default_rng(seed)
    x = np.sort(rng.uniform(-3, 3, n))
    k = np.exp(-0.5 * (x[:, None] - x[None, :]) ** 2)
    return k + (np.trace(k) / (n * cond)) * np.eye(n)


# ------------------------------------------------------------ the op plan


def test_session_one_factorization_many_ops():
    """The whole point: slogdet + solve + adjoint solve + inv on ONE
    factorization, each op verified (Q2-accepted factors, Q3-checked,
    per-round residual checks)."""
    m = _wellcond(12, seed=3)
    b = np.arange(12, dtype=float)
    s = LinalgSession(m, N_SERVERS)
    sign, logabs = s.slogdet()
    y = s.solve(b)
    yt = s.solve(b, transpose=True)
    inv = s.inv()
    assert s.factorizations == 1
    ws, wl = np.linalg.slogdet(m)
    assert sign == ws and np.isclose(logabs, wl, rtol=TOL)
    np.testing.assert_allclose(y, np.linalg.solve(m, b), rtol=0, atol=TOL)
    np.testing.assert_allclose(yt, np.linalg.solve(m.T, b), rtol=0,
                               atol=TOL)
    np.testing.assert_allclose(inv, np.linalg.inv(m), rtol=0, atol=TOL)
    rep = s.report
    ops = [o.op for o in rep.ops]
    assert ops == ["factor", "slogdet", "solve", "solve_t", "inv"]
    assert all(o.verified for o in rep.ops)
    # inv is cached: asking again (either orientation) adds no round
    s.inv(transpose=True)
    assert len(s.report.ops) == len(rep.ops)
    assert s.factorizations == 1


@pytest.mark.parametrize("mode", ["ewd", "ewm"])
@pytest.mark.parametrize("growth_safe", [True, False])
def test_solve_inv_match_numpy_across_cipher_variants(mode, growth_safe):
    """(mode, growth_safe) × seeds: the case table of B⁻¹ recoveries must
    hold for every rotation degree the seeds land on."""
    seen_k = set()
    for seed in range(6):
        m = _wellcond(9, seed=seed)
        b = np.linspace(-1, 1, 9)
        s = LinalgSession(m, N_SERVERS, mode=mode, growth_safe=growth_safe)
        np.testing.assert_allclose(
            s.solve(b), np.linalg.solve(m, b), rtol=0, atol=TOL
        )
        np.testing.assert_allclose(
            s.inv(), np.linalg.inv(m), rtol=0, atol=TOL
        )
        seen_k.add(s._meta.rotate_k % 4)
    assert len(seen_k) >= 2, "seeds never varied the rotation degree"


def test_solve_matrix_rhs_and_transpose():
    m = _wellcond(10, seed=7)
    b = np.random.default_rng(7).standard_normal((10, 3))
    s = LinalgSession(m, N_SERVERS)
    np.testing.assert_allclose(
        s.solve(b), np.linalg.solve(m, b), rtol=0, atol=TOL
    )
    np.testing.assert_allclose(
        s.solve(b, transpose=True), np.linalg.solve(m.T, b), rtol=0,
        atol=TOL,
    )
    assert s.factorizations == 1


def test_growth_safe_default_survives_spd_kernels():
    """rot90 of an SPD kernel matrix is a catastrophic no-pivot input
    (growth ~1e18 at n=64); the session's growth_safe default must keep
    the GP workload's matrices solvable."""
    m = _spd(24, seed=0, cond=500.0)
    s = LinalgSession(m, N_SERVERS)  # growth_safe unspecified -> ON
    inv = s.inv()
    err = np.linalg.norm(inv @ m - np.eye(24)) / np.linalg.norm(inv)
    assert err < (1e-8 if X64 else 1e-2)


def test_session_rejects_nonsquare_and_bad_rhs():
    with pytest.raises(ValueError, match="square"):
        LinalgSession(np.ones((3, 4)), N_SERVERS)
    s = LinalgSession(_wellcond(6), N_SERVERS)
    with pytest.raises(ValueError, match="does not match"):
        s.solve(np.ones(7))


def test_outsource_solve_facade():
    """The gateway's audited one-shot path: factor+verify+solve inside."""
    m = _wellcond(8, seed=11)
    b = np.ones(8)
    y, s = outsource_solve(m, b, N_SERVERS)
    np.testing.assert_allclose(y, np.linalg.solve(m, b), rtol=0, atol=TOL)
    assert s.factorizations == 1
    yt, _ = outsource_solve(m, b, N_SERVERS, transpose=True)
    np.testing.assert_allclose(yt, np.linalg.solve(m.T, b), rtol=0,
                               atol=TOL)


# ------------------------------------------------- trust boundary invariants


class _RecordingTransport(InlineTransport):
    """Delegate that captures every TriSolveTask the session ships."""

    def __init__(self):
        super().__init__()
        self.shipped = []

    def solve_shards(self, tasks, faults=(), timeout=None):
        self.shipped.extend(tasks)
        return super().solve_shards(tasks, faults=faults, timeout=timeout)


def test_secret_rhs_never_crosses_in_the_clear():
    """Masked rounds ship rhs + X'·C, never the plaintext right-hand side
    (nor its v-scaled sibling); inverse rounds ship only permutation
    columns."""
    m = _wellcond(10, seed=5)
    b = np.random.default_rng(5).standard_normal(10)
    t = _RecordingTransport()
    s = LinalgSession(m, N_SERVERS, transport=t)
    s.solve(b)
    s.inv()
    n = 10
    # the masked solve round ships one single-column chunk; the inverse
    # round fans the n identity columns out wide (the round's transpose
    # flag varies with the cipher's rotation plan, its width does not)
    narrow = [np.asarray(tk.rhs) for tk in t.shipped
              if np.asarray(tk.rhs).shape[1] <= 2]
    assert narrow, "no masked solve-round tasks captured"
    masked = np.concatenate(narrow, axis=1)
    # the pad C has ~‖b‖ scale: the wire chunk must differ from both b
    # and b/v (EWD pre-scaling) everywhere, not just somewhere
    v = s._v
    for cand in (b, b / v):
        assert not np.any(
            np.isclose(masked[:n, 0], cand, rtol=1e-3, atol=1e-9)
        ), "plaintext RHS entries visible on the wire"
    # wide (inverse) round: strictly public entries, a 0/1 permutation
    wide = [np.asarray(tk.rhs) for tk in t.shipped
            if np.asarray(tk.rhs).shape[1] >= n // 2]
    assert wide and all(
        set(np.unique(w.round(12))) <= {0.0, 1.0} for w in wide
    ), "inverse rounds must ship only permutation columns"


def test_blind_rhs_roundtrip_and_freshness():
    rng = np.random.default_rng(0)
    x_aug = rng.standard_normal((12, 12))
    rhs = rng.standard_normal((12, 2))
    digest = b"\x07" * 32
    shipped, c = blind_rhs(rhs, x_aug, digest, 0, 0)
    np.testing.assert_allclose(shipped - x_aug @ c, rhs, atol=1e-12)
    # transpose rounds pad through X'ᵀ
    shipped_t, c_t = blind_rhs(rhs, x_aug, digest, 1, 1)
    np.testing.assert_allclose(shipped_t - x_aug.T @ c_t, rhs, atol=1e-12)
    # fresh pad per round index — no two-time pad
    s2, c2 = blind_rhs(rhs, x_aug, digest, 1, 0)
    assert not np.allclose(c, c2)


def test_probe_lanes_are_domain_separated():
    from repro.linalg.session import _lane_rng

    d = b"\x01" * 32
    a = _lane_rng(d, b"trisolve-probe", 0, 0, 0).standard_normal(8)
    b = _lane_rng(d, b"trisolve-mask", 0, 0, 0).standard_normal(8)
    c = _lane_rng(d, b"trisolve-probe", 0, 0, 1).standard_normal(8)
    again = _lane_rng(d, b"trisolve-probe", 0, 0, 0).standard_normal(8)
    assert not np.allclose(a, b) and not np.allclose(a, c)
    np.testing.assert_array_equal(a, again)


# ------------------------------------------------------------- tamper / heal


def _corrupting(cls):
    """Transport subclass that tampers the first solve chunk of every
    initial dispatch (attempt 0) — the factorization stays honest, so
    the heal under test is the TRISOLVE one."""
    class Corrupting(cls):
        def solve_shards(self, tasks, faults=(), timeout=None):
            out = super().solve_shards(tasks, faults=faults,
                                       timeout=timeout)
            if tasks and tasks[0].attempt == 0:
                from dataclasses import replace
                out[0] = replace(out[0], y=np.asarray(out[0].y) * 3.0)
            return out

    return Corrupting


@pytest.mark.parametrize("transport_cls", [InlineTransport,
                                           ThreadPoolTransport])
def test_trisolve_tamper_localizes_and_heals(transport_cls):
    """A tampered solve chunk fails the per-chunk residual check; the
    round localizes it and recover_solve re-issues to a replacement."""
    m = _wellcond(12, seed=9)
    b = np.random.default_rng(9).standard_normal(12)
    with _corrupting(transport_cls)() as t:
        s = LinalgSession(m, N_SERVERS, transport=t)
        y = s.solve(b)
    np.testing.assert_allclose(y, np.linalg.solve(m, b), rtol=0, atol=TOL)
    rep = s.report
    solve_ops = [o for o in rep.ops if o.op.startswith("solve")]
    assert solve_ops and solve_ops[0].healed >= 1
    assert all(o.verified for o in rep.ops)


@needs_x64
def test_fault_plan_tamper_heals_factorization_and_round():
    """The `faults=` plan corrupts the named server's LU strip AND its
    solve chunks; both layers localize and heal. (f64 only: the f32 Q2
    eps is scale²-widened far past a single-entry tamper, so the f32 leg
    fail-stops at the session's Q3 instead of healing — tested above via
    transport-level corruption.)"""
    m = _wellcond(12, seed=9)
    b = np.random.default_rng(9).standard_normal(12)
    s = LinalgSession(
        m, N_SERVERS, faults=ServerFault(server=0, magnitude=50.0),
    )
    y = s.solve(b)
    np.testing.assert_allclose(y, np.linalg.solve(m, b), rtol=0, atol=TOL)
    assert all(o.verified for o in s.report.ops)
    assert any(o.healed >= 1 for o in s.report.ops)


def test_trisolve_dropout_heals():
    m = _wellcond(10, seed=4)
    s = LinalgSession(
        m, N_SERVERS,
        faults=ServerFault(server=1, kind="dropout"),
    )
    inv = s.inv()
    np.testing.assert_allclose(inv, np.linalg.inv(m), rtol=0, atol=TOL)
    assert any(o.healed >= 1 for o in s.report.ops)


def test_trisolve_tamper_recover_false_raises():
    """Corrupt ONLY the solve round (the factorization stays honest, so
    the failure is the trisolve check, not Authenticate)."""
    class _Tamper(InlineTransport):
        def solve_shards(self, tasks, faults=(), timeout=None):
            out = super().solve_shards(tasks, faults=faults,
                                       timeout=timeout)
            from dataclasses import replace
            out[0] = replace(out[0], y=np.asarray(out[0].y) * 3.0)
            return out

    m = _wellcond(10, seed=2)
    with _Tamper() as t:
        s = LinalgSession(m, N_SERVERS, transport=t, recover=False)
        with pytest.raises(LinalgVerificationError, match="recover=False"):
            s.solve(np.ones(10))


# ----------------------------------------------------------------- wire layer


def test_trisolve_wire_roundtrip():
    rng = np.random.default_rng(1)
    task = TriSolveTask(
        server=1, num_servers=3,
        l=np.tril(rng.standard_normal((6, 6))),
        u=np.triu(rng.standard_normal((6, 6))),
        rhs=rng.standard_normal((6, 2)),
        subseed=b"\xaa" * 16, transpose=1, col0=2, attempt=1,
        session_id="sess-1",
    )
    back = TriSolveTask.from_bytes(task.to_bytes())
    assert (back.server, back.num_servers, back.subseed, back.transpose,
            back.col0, back.attempt, back.session_id) == \
        (1, 3, b"\xaa" * 16, 1, 2, 1, "sess-1")
    np.testing.assert_array_equal(back.l, task.l)
    np.testing.assert_array_equal(back.u, task.u)
    np.testing.assert_array_equal(back.rhs, task.rhs)
    assert back.n == 6 and back.cols == 2

    res = TriSolveResult(server=1, y=rng.standard_normal((6, 2)),
                         subseed=b"\xbb" * 16, transpose=1, col0=2,
                         attempt=1, session_id="sess-1")
    rback = TriSolveResult.from_bytes(res.to_bytes())
    np.testing.assert_array_equal(rback.y, res.y)
    assert rback.subseed == b"\xbb" * 16 and rback.col0 == 2


def test_stale_echo_rejected():
    """A replayed chunk from another dispatch fails the echo binding
    before any math — and heals."""
    class _Replay(InlineTransport):
        def solve_shards(self, tasks, faults=(), timeout=None):
            out = super().solve_shards(tasks, faults=faults,
                                       timeout=timeout)
            if tasks and tasks[0].attempt == 0:
                from dataclasses import replace
                out[0] = replace(out[0], subseed=b"\x00" * 16)
            return out

    m = _wellcond(10, seed=6)
    with _Replay() as t:
        s = LinalgSession(m, N_SERVERS, transport=t)
        y = s.solve(np.ones(10))
    np.testing.assert_allclose(y, np.linalg.solve(m, np.ones(10)),
                               rtol=0, atol=TOL)
    assert any(o.healed >= 1 for o in s.report.ops)


# ------------------------------------------------------- differentiable ops


def test_secure_ops_forward_match():
    m = _wellcond(10, seed=8)
    b = np.random.default_rng(8).standard_normal(10)
    ctx = SecureLinalg(N_SERVERS)
    sign, logabs = secure_slogdet(m, linalg=ctx)
    y = secure_solve(m, b, linalg=ctx)
    inv = secure_inv(m, linalg=ctx)
    ws, wl = np.linalg.slogdet(m)
    assert float(sign) == ws and np.isclose(float(logabs), wl, rtol=TOL)
    np.testing.assert_allclose(np.asarray(y), np.linalg.solve(m, b),
                               rtol=0, atol=TOL)
    np.testing.assert_allclose(np.asarray(inv), np.linalg.inv(m),
                               rtol=0, atol=TOL)
    # all three ops (and their rounds) on one session, one factorization
    assert len(ctx._sessions) == 1
    assert sum(s.factorizations for s in ctx._sessions.values()) == 1


def test_secure_ops_validate_shapes():
    ctx = SecureLinalg(N_SERVERS)
    with pytest.raises(ValueError, match="square"):
        secure_slogdet(jnp.ones((2, 3)), linalg=ctx)
    with pytest.raises(ValueError, match="square"):
        secure_inv(jnp.ones((2, 3)), linalg=ctx)
    with pytest.raises(ValueError, match="rhs shape"):
        secure_solve(jnp.eye(3), jnp.ones(4), linalg=ctx)


@needs_x64
def test_gp_loglik_grad_matches_reference():
    """The acceptance bar: jax.grad of a jitted GP log-likelihood through
    secure_slogdet + secure_solve matches the plaintext reference to
    1e-6, with Q2+Q3-verified ops and exactly one factorization."""
    n = 24
    rng = np.random.default_rng(0)
    x = jnp.asarray(np.sort(rng.uniform(-3, 3, n)))
    yv = jnp.asarray(np.sin(2 * np.asarray(x))
                     + 0.1 * rng.standard_normal(n))
    ctx = SecureLinalg(N_SERVERS)

    def cov(theta):
        d2 = (x[:, None] - x[None, :]) ** 2
        k = jnp.exp(2 * theta[1]) * jnp.exp(
            -0.5 * d2 / jnp.exp(2 * theta[0]))
        return k + jnp.exp(2 * theta[2]) * jnp.eye(n)

    def nll_secure(theta):
        c = cov(theta)
        _, logdet = secure_slogdet(c, linalg=ctx)
        alpha = secure_solve(c, yv, linalg=ctx)
        return 0.5 * (logdet + yv @ alpha)

    def nll_ref(theta):
        c = cov(theta)
        _, logdet = jnp.linalg.slogdet(c)
        return 0.5 * (logdet + yv @ jnp.linalg.solve(c, yv))

    theta = jnp.asarray([np.log(0.8), 0.0, np.log(0.2)])
    val, grad = jax.jit(jax.value_and_grad(nll_secure))(theta)
    rval, rgrad = jax.jit(jax.value_and_grad(nll_ref))(theta)
    assert np.isclose(float(val), float(rval), rtol=1e-9)
    gerr = float(jnp.max(jnp.abs(grad - rgrad))
                 / (jnp.max(jnp.abs(rgrad)) + 1e-30))
    assert gerr < 1e-6, gerr
    sessions = list(ctx._sessions.values())
    assert len(sessions) == 1 and sessions[0].factorizations == 1
    assert all(o.verified for o in sessions[0].report.ops)


def test_grad_works_without_x64_leg():
    """The f32 leg still differentiates end-to-end (looser bar)."""
    m = _wellcond(8, seed=10)
    ctx = SecureLinalg(N_SERVERS)

    def f(a):
        _, logdet = secure_slogdet(a, linalg=ctx)
        return logdet

    g = jax.grad(f)(jnp.asarray(m))
    ref = np.linalg.inv(m).T
    np.testing.assert_allclose(np.asarray(g), ref, rtol=0,
                               atol=1e-8 if X64 else 1e-2)
    assert sum(s.factorizations for s in ctx._sessions.values()) == 1


def test_solve_vjp_adjoint_round():
    """b̄ = M⁻ᵀz̄ comes back through the same session; ā = −b̄zᵀ."""
    m = _wellcond(8, seed=12)
    b = np.random.default_rng(12).standard_normal(8)
    ctx = SecureLinalg(N_SERVERS)

    def f(a, rhs):
        z = secure_solve(a, rhs, linalg=ctx)
        return jnp.sum(z ** 2)

    ga, gb = jax.grad(f, argnums=(0, 1))(jnp.asarray(m), jnp.asarray(b))
    z = np.linalg.solve(m, b)
    gbar = np.linalg.solve(m.T, 2 * z)
    np.testing.assert_allclose(np.asarray(gb), gbar, rtol=0,
                               atol=1e-8 if X64 else 1e-2)
    np.testing.assert_allclose(np.asarray(ga), -np.outer(gbar, z),
                               rtol=0, atol=1e-8 if X64 else 1e-2)
    assert sum(s.factorizations for s in ctx._sessions.values()) == 1


def test_session_cache_eviction():
    ctx = SecureLinalg(N_SERVERS, max_sessions=2)
    for seed in range(3):
        ctx.session_for(_wellcond(6, seed=seed))
    assert len(ctx._sessions) == 2
    ctx.clear()
    assert not ctx._sessions


def test_host_thread_keeps_the_callers_default_device():
    """pure_callback runs its body with the host CPU as default device; the
    hop onto the linalg host thread must keep it (on a TPU the protocol
    would otherwise queue on the chip behind the program waiting on it)."""
    from repro.linalg.ops import _on_host_thread

    placed = _on_host_thread(lambda: jnp.zeros(()).devices())
    other = jax.devices()[-1]
    with jax.default_device(other):
        assert placed() == {other}
    assert placed() == {jax.devices()[0]}
