"""Distributed N-server SPDC LU — paper Algorithm 3 as a shard_map pipeline.

Mapping (DESIGN.md §2): edge server i ⇒ mesh device i on a 1-D "servers"
axis. Server i owns block row i of the ciphered matrix (in_specs
P("servers", None)). The paper's one-way communication pattern — S_i sends
its accumulated U rows only to S_{i+1} — becomes a single forward
`lax.ppermute` per round: neighbor-only ICI traffic, no broadcast, no
all-gather, exactly the paper's §IV.D.3 schedule.

Program structure (SPMD, N rounds):

  round t:  device with axis_index == t runs its Alg.-3 row computation
            (L_{t,0..t-1} via TRSM against upstream U; blocked-panel LU of
            the Schur-updated diagonal block; its U row), writes the U row
            into the relay buffer; then every device forwards the relay
            buffer one hop down the ring.

Batch semantics (DESIGN.md §3): every program accepts a device-local block
of shape (b, n) — one matrix — or (B, b, n) — a stack. The batch dimension
stays device-local (in_specs P(None, "servers", None)); the "servers" axis
and the relay schedule are unchanged, so a single N-round wavefront sweep
factors all B matrices: the N-1 relay hops are paid once per batch instead
of once per matrix.

The relay buffer is the fixed-shape (n, n) U matrix (rows ≥ t still zero).
The paper's variable-size messages (rows 0..t only) would be a ragged
send; fixed-shape relay overcounts bytes by ≤ 2× — accounted for in
benchmarks (CommLog tracks the paper-exact volume).

The per-device active computation is gated behind `lax.cond` on the traced
axis index, so passive devices do no FLOPs while the wavefront is
elsewhere — faithful to the paper's staggered activation (§IV.D.3).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.core.lu import precise_matmul


def _factor_diag(a: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-round diagonal factorization: the blocked panel for b >= 64 (no
    full-tile Doolittle on the critical path), plain Doolittle below."""
    from repro.core.lu import lu_diag_factor

    return lu_diag_factor(a)


def _batched_view(x_blk: jnp.ndarray, b: int, n: int) -> tuple[jnp.ndarray, bool]:
    """Normalize a device-local block to (B, b, n); remember if it was 2-D."""
    if x_blk.ndim == 3:
        return x_blk, True
    return x_blk.reshape(1, b, n), False


def _trsm_right_upper_b(u: jnp.ndarray, acc: jnp.ndarray) -> jnp.ndarray:
    """L_ik = acc @ U_kk^{-1}, batched over the leading dim."""
    from repro.core.lu import _trsm_right_upper

    return _trsm_right_upper(u, acc)


def _inject_faults(l_row, u_row, my_id, faults, *, n, batched):
    """Device-output fault injection (core.faults surface, distributed leg).

    The mesh device playing the faulty server corrupts the (B, b, n) strips
    it reports — tamper modes and dropouts are first-class on the real
    pipeline, not just the single-process simulation. Faults are static
    (part of the compile cache key); the injection is a `where` on the
    traced axis index, so honest devices' outputs pass through untouched.
    In-band relay poisoning is NOT modeled here (see core.lu.lu_nserver).
    """
    import numpy as np

    from repro.core.faults import corrupt_strip

    for f in faults:
        targets = ("l", "u") if f.kind == "dropout" else tuple(f.target)

        def masked(orig, factor, f=f):
            bad = corrupt_strip(orig, f, n=n, factor=factor)
            if f.matrices is not None and batched:
                idx = np.asarray(f.matrices, dtype=np.int32)
                bad = orig.at[idx].set(bad[idx])
            return jnp.where(my_id == f.server, bad, orig)

        if "l" in targets:
            l_row = masked(l_row, "l")
        if "u" in targets:
            u_row = masked(u_row, "u")
    return l_row, u_row


def _server_program(x_blk: jnp.ndarray, *, n: int, b: int, num_servers: int,
                    axis: str, faults=()) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Runs on every device inside shard_map. x_blk: (b, n) or (B, b, n)."""
    my_id = lax.axis_index(axis)
    x_row, batched = _batched_view(x_blk, b, n)
    B = x_row.shape[0]
    zero = jnp.zeros((), jnp.int32)

    def active(args):
        u_buf, l_row, u_row = args  # (B,n,n), (B,b,n), (B,b,n)

        # --- L_{i,k} for k < i (sequential in k; TRSM vs upstream U_kk) ---
        def lblk(k, l_row):
            kb = (k * b).astype(jnp.int32)
            # slice the U column panel FIRST: O(b·n·b) per step instead of
            # recomputing the full (b,n) product (§Perf C2 — 16x fewer flops
            # in the L-row loop)
            u_col = lax.dynamic_slice(u_buf, (zero, zero, kb), (B, n, b))
            acc = (lax.dynamic_slice(x_row, (zero, zero, kb), (B, b, b))
                   - precise_matmul(l_row, u_col))
            ukk = lax.dynamic_slice(u_buf, (zero, kb, kb), (B, b, b))
            lik = _trsm_right_upper_b(ukk, acc)
            return lax.dynamic_update_slice(l_row, lik, (zero, zero, kb))

        l_row = lax.fori_loop(0, my_id, lblk, l_row)

        # --- Schur update of the whole row, blocked-panel LU of the diag ---
        s = x_row - precise_matmul(l_row, u_buf)
        ib = (my_id * b).astype(jnp.int32)
        sii = lax.dynamic_slice(s, (zero, zero, ib), (B, b, b))
        lii, uii = _factor_diag(sii)
        l_row = lax.dynamic_update_slice(l_row, lii, (zero, zero, ib))

        # --- U_{i,j} for j >= i, vectorized over the full row ---
        r = jax.scipy.linalg.solve_triangular(lii, s, lower=True, unit_diagonal=True)
        cols = lax.broadcasted_iota(jnp.int32, (B, b, n), 2)
        u_row = jnp.where(cols >= ib, r, jnp.zeros_like(r))
        u_buf = lax.dynamic_update_slice(u_buf, u_row, (zero, ib, zero))
        return u_buf, l_row, u_row

    def passive(args):
        return args

    fwd = [(i, (i + 1) % num_servers) for i in range(num_servers)]

    def round_fn(t, state):
        u_buf, l_row, u_row = state
        u_buf, l_row, u_row = lax.cond(
            my_id == t, active, passive, (u_buf, l_row, u_row)
        )
        # one-way relay S_t -> S_{t+1} (ring hop; only the t -> t+1 edge
        # carries fresh data, matching the paper's single send per phase)
        u_buf = lax.ppermute(u_buf, axis, fwd)
        return u_buf, l_row, u_row

    u_buf0 = jnp.zeros((B, n, n), dtype=x_row.dtype)
    l_row0 = jnp.zeros((B, b, n), dtype=x_row.dtype)
    u_row0 = jnp.zeros((B, b, n), dtype=x_row.dtype)
    # carries become device-varying inside the loop; mark them so upfront
    u_buf0, l_row0, u_row0 = lax.pcast(
        (u_buf0, l_row0, u_row0), (axis,), to="varying"
    )
    _, l_row, u_row = lax.fori_loop(
        0, num_servers, round_fn, (u_buf0, l_row0, u_row0)
    )
    if faults:
        l_row, u_row = _inject_faults(l_row, u_row, my_id, faults, n=n,
                                      batched=batched)
    if not batched:
        return l_row[0], u_row[0]
    return l_row, u_row


def _server_program_exact(x_blk: jnp.ndarray, *, n: int, b: int,
                          num_servers: int, axis: str, faults=()):
    """Exact-relay variant (§Perf optimization, beyond-paper): rounds are
    unrolled (num_servers is static) so hop t ppermutes ONLY the U rows
    0..t computed so far — (t+1)·b×n elements instead of the fixed n×n
    relay. Total wire volume drops from N·n² to n²(N+1)/2 (≈2× less), and
    matches the paper's §IV.D.3 message contents exactly.
    """
    my_id = lax.axis_index(axis)
    x_row, batched = _batched_view(x_blk, b, n)
    B = x_row.shape[0]
    fwd = [(i, (i + 1) % num_servers) for i in range(num_servers)]
    zero = jnp.zeros((), jnp.int32)

    def active_fn(args):
        u_buf, l_row, u_row = args

        def lblk(k, l_row):
            kb = (k * b).astype(jnp.int32)
            u_col = lax.dynamic_slice(u_buf, (zero, zero, kb), (B, n, b))
            acc = (lax.dynamic_slice(x_row, (zero, zero, kb), (B, b, b))
                   - precise_matmul(l_row, u_col))
            ukk = lax.dynamic_slice(u_buf, (zero, kb, kb), (B, b, b))
            lik = _trsm_right_upper_b(ukk, acc)
            return lax.dynamic_update_slice(l_row, lik, (zero, zero, kb))

        l_row = lax.fori_loop(0, my_id, lblk, l_row)
        s = x_row - precise_matmul(l_row, u_buf)
        ib = (my_id * b).astype(jnp.int32)
        sii = lax.dynamic_slice(s, (zero, zero, ib), (B, b, b))
        lii, _ = _factor_diag(sii)
        l_row = lax.dynamic_update_slice(l_row, lii, (zero, zero, ib))
        r = jax.scipy.linalg.solve_triangular(lii, s, lower=True,
                                              unit_diagonal=True)
        cols = lax.broadcasted_iota(jnp.int32, (B, b, n), 2)
        u_row = jnp.where(cols >= ib, r, jnp.zeros_like(r))
        u_buf = lax.dynamic_update_slice(u_buf, u_row, (zero, ib, zero))
        return u_buf, l_row, u_row

    u_buf = jnp.zeros((B, n, n), dtype=x_row.dtype)
    l_row = jnp.zeros((B, b, n), dtype=x_row.dtype)
    u_row = jnp.zeros((B, b, n), dtype=x_row.dtype)
    u_buf, l_row, u_row = lax.pcast(
        (u_buf, l_row, u_row), (axis,), to="varying"
    )
    for t in range(num_servers):
        u_buf, l_row, u_row = lax.cond(
            my_id == t, active_fn, lambda a: a, (u_buf, l_row, u_row)
        )
        if t + 1 < num_servers:
            # relay exactly rows 0..t (static slice — rounds are unrolled)
            chunk = lax.ppermute(u_buf[:, : (t + 1) * b], axis, fwd)
            u_buf = u_buf.at[:, : (t + 1) * b].set(chunk)
    if faults:
        l_row, u_row = _inject_faults(l_row, u_row, my_id, faults, n=n,
                                      batched=batched)
    if not batched:
        return l_row[0], u_row[0]
    return l_row, u_row


def _server_program_stream(x_blk: jnp.ndarray, *, n: int, b: int,
                           num_servers: int, axis: str, faults=()):
    """Streaming variant (§Perf C3): no (n,n) relay buffer at all. Each
    round's live state is exactly the received U rows ((t·b, n), a static
    shape per unrolled round); the active server computes against that row
    set and appends its own row before the hop. Wire volume equals the
    exact relay; local HBM traffic drops by the (n,n) buffer copies.
    """
    my_id = lax.axis_index(axis)
    x_row, batched = _batched_view(x_blk, b, n)
    B = x_row.shape[0]
    fwd = [(i, (i + 1) % num_servers) for i in range(num_servers)]
    zero = jnp.zeros((), jnp.int32)

    l_row = jnp.zeros((B, b, n), dtype=x_row.dtype)
    u_row = jnp.zeros((B, b, n), dtype=x_row.dtype)
    l_row, u_row = lax.pcast((l_row, u_row), (axis,), to="varying")
    # _stream_rows[t] = rows received before round t ((B, t·b, n), static)
    _stream_rows = [
        lax.pcast(jnp.zeros((B, t * b, n), dtype=x_row.dtype), (axis,),
              to="varying")
        for t in range(num_servers)
    ]

    for t in range(num_servers):
        def active_fn(args, t=t):
            l_row, u_row = args
            tb = t * b
            u_recv = _stream_rows[t]  # (B, tb, n) received rows, static shape

            def lblk(k, l_row):
                kb = (k * b).astype(jnp.int32)
                u_col = lax.dynamic_slice(u_recv, (zero, zero, kb), (B, tb, b))
                acc = lax.dynamic_slice(x_row, (zero, zero, kb), (B, b, b)) \
                    - precise_matmul(l_row[:, :, :tb], u_col)
                ukk = lax.dynamic_slice(u_recv, (zero, kb, kb), (B, b, b))
                lik = _trsm_right_upper_b(ukk, acc)
                return lax.dynamic_update_slice(l_row, lik, (zero, zero, kb))

            if t:
                l_row = lax.fori_loop(0, t, lblk, l_row)
                s = x_row - precise_matmul(l_row[:, :, :tb], u_recv)
            else:
                s = x_row
            ib = jnp.asarray(t * b, jnp.int32)
            sii = lax.dynamic_slice(s, (zero, zero, ib), (B, b, b))
            lii, _ = _factor_diag(sii)
            l_row = lax.dynamic_update_slice(l_row, lii, (zero, zero, ib))
            r = jax.scipy.linalg.solve_triangular(lii, s, lower=True,
                                                  unit_diagonal=True)
            cols = lax.broadcasted_iota(jnp.int32, (B, b, n), 2)
            u_row = jnp.where(cols >= ib, r, jnp.zeros_like(r))
            return l_row, u_row

        l_row, u_row = lax.cond(
            my_id == t, active_fn, lambda a: a, (l_row, u_row)
        )
        if t + 1 < num_servers:
            # append the active server's row to the stream and hop. Passive
            # devices forward the rows they were relayed (garbage until a
            # device is about to activate, at which point it has received
            # the genuine rows 0..t from its true upstream chain).
            send = jnp.concatenate(
                [_stream_rows[t],
                 jnp.where(my_id == t, u_row, jnp.zeros_like(u_row))],
                axis=1,
            )
            _stream_rows[t + 1] = lax.ppermute(send, axis, fwd)
    if faults:
        l_row, u_row = _inject_faults(l_row, u_row, my_id, faults, n=n,
                                      batched=batched)
    if not batched:
        return l_row[0], u_row[0]
    return l_row, u_row


_PROGRAMS = {
    "baseline": _server_program,
    "exact": _server_program_exact,
    "stream": _server_program_stream,
}


@lru_cache(maxsize=None)
def _compiled_pipeline(program: str, n: int, batch: int | None,
                       num_servers: int, axis: str, faults=()):
    """Build + jit one pipeline program on the default device mesh.

    Cached so repeated protocol calls (the high-throughput serving path)
    reuse the compiled executable instead of re-tracing a fresh shard_map.
    """
    devs = tuple(jax.devices()[:num_servers])
    mesh = jax.make_mesh((num_servers,), (axis,),
                         axis_types=(AxisType.Auto,), devices=devs)
    b = n // num_servers
    spec = P(None, axis, None) if batch is not None else P(axis, None)
    fn = jax.shard_map(
        partial(_PROGRAMS[program], n=n, b=b, num_servers=num_servers,
                axis=axis, faults=faults),
        mesh=mesh,
        in_specs=spec,
        out_specs=(spec, spec),
    )
    return jax.jit(fn)


def lu_nserver_shardmap(
    x: jnp.ndarray, num_servers: int, *, mesh=None, axis: str = "servers",
    program: str = "baseline", faults=(),
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Distributed Alg. 3. x: (n, n) or (B, n, n) with n % num_servers == 0.

    program: one of "baseline" (fixed-shape relay), "exact" (paper-exact
    ragged relay), "stream" (no relay buffer; received rows only). The
    batch dimension, if present, stays device-local — one wavefront sweep
    factors the whole stack (DESIGN.md §3).

    faults: a FaultPlan (core.faults) injected at the device-output level:
    the mesh device playing each faulty server corrupts (or zeroes) the
    strips it reports. Delay faults must be resolved by the caller
    (core.faults.resolve_delays); in-band relay poisoning is only modeled
    by the single-process simulation and is rejected here.

    mesh: optional existing mesh containing `axis`; default builds a 1-D
    mesh over the first num_servers devices of this process.

    (The deprecated `exact_relay=` bool shim completed its cycle and was
    removed — passing it now raises TypeError.)
    """
    if program not in _PROGRAMS:
        raise ValueError(
            f"unknown program {program!r}; expected one of {sorted(_PROGRAMS)}"
        )
    from repro.core.faults import normalize_plan

    faults = normalize_plan(faults)
    if any(f.in_band for f in faults):
        raise ValueError(
            "in_band faults are not modeled by the shard_map pipeline; use "
            "core.lu.lu_nserver for relay-poisoning simulation"
        )
    if any(f.kind == "delay" for f in faults):
        raise ValueError(
            "resolve delay faults first (core.faults.resolve_delays)"
        )
    n = x.shape[-1]
    if x.ndim not in (2, 3):
        raise ValueError(f"x must be (n, n) or (B, n, n), got shape {x.shape}")
    if n % num_servers != 0 or n // num_servers <= 1:
        raise ValueError(f"n={n} not partitionable over N={num_servers}; augment first")
    batch = x.shape[0] if x.ndim == 3 else None

    if mesh is None:
        devices = jax.devices()
        if len(devices) < num_servers:
            raise ValueError(
                f"the shard_map pipeline needs one device per server: "
                f"N={num_servers}, but the {devices[0].platform} backend "
                f"has {len(devices)} device(s)"
            )
        fn = _compiled_pipeline(program, n, batch, num_servers, axis, faults)
    else:
        b = n // num_servers
        spec = P(None, axis, None) if batch is not None else P(axis, None)
        fn = jax.jit(jax.shard_map(
            partial(_PROGRAMS[program], n=n, b=b, num_servers=num_servers,
                    axis=axis, faults=faults),
            mesh=mesh,
            in_specs=spec,
            out_specs=(spec, spec),
        ))
    l, u = fn(x)
    return l, u


def pipeline_collective_bytes(n: int, num_servers: int, itemsize: int = 8) -> dict:
    """Communication model: fixed-shape relay vs the paper's exact volume."""
    relay = num_servers * n * n * itemsize  # one (n,n) hop per round
    paper = sum(
        sum((num_servers - k) for k in range(i + 1)) * (n // num_servers) ** 2
        for i in range(num_servers - 1)
    ) * itemsize
    return {"relay_bytes": relay, "paper_exact_bytes": paper,
            "overcount_factor": relay / max(paper, 1)}
