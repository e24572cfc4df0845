"""Differentiable secure ops — `jax.custom_vjp` over the shared LU.

`secure_slogdet` / `secure_solve` / `secure_inv` are jit-compatible jax
functions whose FORWARD value comes from the outsourced protocol (a
`jax.pure_callback` into a `LinalgSession`) and whose VJPs route through
the SAME verified factors:

    ∂ log|det M| / ∂M = M⁻ᵀ          (one wide identity-RHS round, cached)
    z = M⁻¹b:   b̄ = M⁻ᵀz̄            (one masked adjoint round)
                M̄ = −b̄ · zᵀ          (client-side outer product)
    Y = M⁻¹:    M̄ = −Yᵀ·Ȳ·Yᵀ        (client-side, no extra round)

so a gradient step through slogdet + solve costs ONE factorization plus
a handful of O(n²)-client triangular-solve rounds — and nothing new
crosses the trust boundary in the backward pass: the adjoint rounds ship
the same blinded/public RHS shapes the forward ops do (linalg.session).

Sessions are cached per matrix VALUE (SHA-256 of bytes ‖ shape ‖ dtype)
on a `SecureLinalg` context, which is how the forward slogdet, the
forward solve, and both backward passes of one training step land on a
single factorization.  The callback pattern is sound because the
protocol is deterministic in the matrix bytes: seeds, keys, masks, and
probes all derive from SHA-256 of the plaintext, so re-execution under
jit replay returns bit-identical values.
"""
from __future__ import annotations

import concurrent.futures
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lu import precise_matmul

from .session import LinalgSession

__all__ = [
    "SecureLinalg", "default_linalg",
    "secure_slogdet", "secure_solve", "secure_inv",
]


class SecureLinalg:
    """Session cache + protocol configuration for the differentiable ops.

    One context = one fleet configuration (num_servers, transport,
    client knobs).  `session_for` returns the LinalgSession for a matrix
    value, opening one on first sight — every op and every VJP that sees
    the same bytes shares it, so `session.factorizations` stays 1 across
    a whole gradient step.
    """

    def __init__(self, num_servers: int = 2, *, transport=None,
                 max_sessions: int = 8, **session_kwargs):
        _disable_cpu_async_dispatch()
        self.num_servers = num_servers
        self.transport = transport
        self.session_kwargs = session_kwargs
        self.max_sessions = max_sessions
        self._sessions: dict = {}

    def session_for(self, a: np.ndarray) -> LinalgSession:
        a = np.ascontiguousarray(a)
        key = (hashlib.sha256(a.tobytes()).digest(), a.shape, str(a.dtype))
        s = self._sessions.get(key)
        if s is None:
            s = LinalgSession(a, self.num_servers,
                              transport=self.transport,
                              **self.session_kwargs)
            self._sessions[key] = s
            while len(self._sessions) > self.max_sessions:
                # dicts iterate in insertion order: evict the oldest
                self._sessions.pop(next(iter(self._sessions)))
        return s

    def clear(self) -> None:
        self._sessions.clear()


def _disable_cpu_async_dispatch() -> None:
    """Nested-dispatch deadlock guard, applied at import and per context.

    XLA:CPU's async dispatch runs expensive jitted programs on a single
    dispatch queue. A pure_callback inside such a program re-enters jax
    (the protocol's cipher/sweep/verify jits) and blocks on the result —
    which queues behind the very program waiting on the callback. Cheap
    outer graphs dodge this by executing inline, which is why the hang
    only shows once the operand has real in-graph producers (e.g. a
    kernel matrix built from hyperparameters). Synchronous dispatch makes
    re-entry safe at a small dispatch/compute overlap cost.

    The option is read ONCE, when the CPU client is created, so this must
    run before the first jax dispatch of the process — importing
    `repro.linalg` does it, hence the module-level call below. If the
    backend already exists the update is a silent no-op upstream, so warn
    loudly instead of deadlocking quietly later.
    """
    # the option is a Flag, not a State: jax.config.update accepts it but
    # jax.config has no attribute to read it back through
    from jax._src import xla_bridge as _xb

    if not _xb._CPU_ENABLE_ASYNC_DISPATCH.value:
        return  # already off (this guard earlier, or the user)
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    if _xb._backends:
        import warnings

        warnings.warn(
            "repro.linalg was imported after jax initialized its CPU "
            "backend; jax_cpu_enable_async_dispatch cannot take effect, "
            "and jit-compiled secure ops may deadlock on nested "
            "dispatch. Import repro.linalg first (or start the process "
            "with JAX_CPU_ENABLE_ASYNC_DISPATCH=0).",
            RuntimeWarning,
            stacklevel=3,
        )


_disable_cpu_async_dispatch()

_default: SecureLinalg | None = None


def default_linalg() -> SecureLinalg:
    """The module-default context (2 inline servers), built lazily."""
    global _default
    if _default is None:
        _default = SecureLinalg()
    return _default


def _np(x):
    return np.asarray(x)


#: Every callback body hops to this single plain Python thread. XLA may
#: invoke pure_callbacks from several of its own threads at once (fwd and
#: bwd callbacks of one step, or steps racing across user threads); the
#: one-worker hop serializes them onto the unsynchronized session cache
#: and keeps the protocol's transports single-threaded, as every other
#: client entry point does. (It does NOT fix the nested-dispatch
#: deadlock — see _disable_cpu_async_dispatch for that.)
_HOST_POOL = concurrent.futures.ThreadPoolExecutor(
    max_workers=1, thread_name_prefix="repro-linalg-host"
)


def _on_host_thread(fn):
    """Run `fn` on _HOST_POOL under the caller's default device.

    jax runs a pure_callback with the host CPU as the default device, so
    the protocol programs the callback dispatches run on the CPU while
    the device program that called it waits. That default is thread-local
    and would not survive the hop: on a TPU the protocol would then queue
    its programs on the chip behind the very program blocked on them.
    """
    @functools.wraps(fn)
    def wrapper(*args):
        device = jax.config.jax_default_device

        def call():
            with jax.default_device(device):
                return fn(*args)

        return _HOST_POOL.submit(call).result()

    return wrapper


# -- slogdet ----------------------------------------------------------------

def _slogdet_impl(ctx, a):
    @_on_host_thread
    def cb(a_np):
        s = ctx.session_for(_np(a_np))
        sign, logabs = s.slogdet()
        dt = _np(a_np).dtype
        return np.asarray(sign, dtype=dt), np.asarray(logabs, dtype=dt)

    out_shape = (jax.ShapeDtypeStruct((), a.dtype),
                 jax.ShapeDtypeStruct((), a.dtype))
    return jax.pure_callback(cb, out_shape, a)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _slogdet(ctx, a):
    return _slogdet_impl(ctx, a)


def _slogdet_fwd(ctx, a):
    return _slogdet_impl(ctx, a), a


def _slogdet_bwd(ctx, a, ct):
    _, g_logabs = ct  # sign is locally constant, its cotangent drops

    @_on_host_thread
    def cb(a_np, g_np):
        s = ctx.session_for(_np(a_np))
        return (_np(g_np) * s.inv(transpose=True)).astype(_np(a_np).dtype)

    abar = jax.pure_callback(
        cb, jax.ShapeDtypeStruct(a.shape, a.dtype), a, g_logabs
    )
    return (abar,)


_slogdet.defvjp(_slogdet_fwd, _slogdet_bwd)


def secure_slogdet(a, *, linalg: SecureLinalg | None = None):
    """(sign, log|det a|) via the outsourced protocol; differentiable.

    Drop-in for `jnp.linalg.slogdet` on one (n, n) matrix.  The gradient
    of log|det| is M⁻ᵀ, computed through the session's shared verified
    factors — no fresh factorization, no new plaintext on the wire.
    """
    ctx = linalg if linalg is not None else default_linalg()
    a = jnp.asarray(a)
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        raise ValueError(f"secure_slogdet needs a square matrix, got "
                         f"{a.shape}")
    return _slogdet(ctx, a)


# -- solve ------------------------------------------------------------------

def _solve_impl(ctx, a, b):
    @_on_host_thread
    def cb(a_np, b_np):
        s = ctx.session_for(_np(a_np))
        return s.solve(_np(b_np)).astype(_np(b_np).dtype)

    return jax.pure_callback(
        cb, jax.ShapeDtypeStruct(b.shape, b.dtype), a, b
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _solve(ctx, a, b):
    return _solve_impl(ctx, a, b)


def _solve_fwd(ctx, a, b):
    z = _solve_impl(ctx, a, b)
    return z, (a, z)


def _solve_bwd(ctx, res, zbar):
    a, z = res

    @_on_host_thread
    def cb(a_np, g_np):
        s = ctx.session_for(_np(a_np))
        return s.solve(_np(g_np), transpose=True).astype(_np(g_np).dtype)

    bbar = jax.pure_callback(
        cb, jax.ShapeDtypeStruct(zbar.shape, zbar.dtype), a, zbar
    )
    if z.ndim == 1:
        abar = -jnp.outer(bbar, z)
    else:
        abar = -precise_matmul(bbar, z.T)
    return abar, bbar


_solve.defvjp(_solve_fwd, _solve_bwd)


def secure_solve(a, b, *, linalg: SecureLinalg | None = None):
    """a x = b through the session's shared verified LU; differentiable.

    Drop-in for `jnp.linalg.solve` with b of shape (n,) or (n, c).  The
    adjoint b̄ = a⁻ᵀz̄ is ONE extra masked triangular-solve round through
    the same factors; ā = −b̄ zᵀ needs no round at all.
    """
    ctx = linalg if linalg is not None else default_linalg()
    a, b = jnp.asarray(a), jnp.asarray(b)
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        raise ValueError(f"secure_solve needs a square matrix, got "
                         f"{a.shape}")
    if b.ndim not in (1, 2) or b.shape[0] != a.shape[0]:
        raise ValueError(
            f"rhs shape {b.shape} does not match matrix {a.shape}"
        )
    return _solve(ctx, a, b)


# -- inv --------------------------------------------------------------------

def _inv_impl(ctx, a):
    @_on_host_thread
    def cb(a_np):
        s = ctx.session_for(_np(a_np))
        return s.inv().astype(_np(a_np).dtype)

    return jax.pure_callback(
        cb, jax.ShapeDtypeStruct(a.shape, a.dtype), a
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _inv(ctx, a):
    return _inv_impl(ctx, a)


def _inv_fwd(ctx, a):
    y = _inv_impl(ctx, a)
    return y, y


def _inv_bwd(ctx, y, ybar):
    # d(A⁻¹) = −A⁻¹ dA A⁻¹  ⇒  Ā = −Yᵀ Ȳ Yᵀ: pure jax-land, the wide
    # round already ran (and is cached) in the forward pass
    return (-precise_matmul(precise_matmul(y.T, ybar), y.T),)


_inv.defvjp(_inv_fwd, _inv_bwd)


def secure_inv(a, *, linalg: SecureLinalg | None = None):
    """inv(a) via one wide public-permutation-RHS round; differentiable."""
    ctx = linalg if linalg is not None else default_linalg()
    a = jnp.asarray(a)
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        raise ValueError(f"secure_inv needs a square matrix, got {a.shape}")
    return _inv(ctx, a)
