"""Distributed N-server SPDC LU — paper Algorithm 3 as a shard_map pipeline.

Mapping (DESIGN.md §2): the N edge servers lie on a 1-D "servers" axis of
D chips, k = N/D consecutive servers per chip — server s on chip s // k.
D is the largest divisor of N that is at most the process's device count
(`relay_chips`), so k = 1 wherever there is a device per server. A chip
owns the block rows of its servers, a (k·b, n) slab of the ciphered
matrix (in_specs P("servers", None)). The paper's one-way communication
pattern — S_i sends its accumulated U rows only to S_{i+1} — is a local
write into the chip's U buffer between two servers on one chip, and a
single forward `lax.ppermute` where the chain crosses to the next chip:
neighbor-only ICI traffic, no broadcast, no all-gather, the paper's
§IV.D.3 schedule.

Program structure (SPMD, D chip-rounds):

  chip-round d:  the chip with axis_index == d runs its k servers in
                 order; server t = d·k + j runs its Alg.-3 row computation
                 (L_{t,0..t-1} via TRSM against upstream U; blocked-panel
                 LU of the Schur-updated diagonal block; its U row) and
                 writes the U row into the chip's relay buffer, where
                 server t+1 reads it; then every chip forwards the relay
                 buffer one hop down the ring.

Batch semantics (DESIGN.md §3): every program accepts a device-local block
of shape (k·b, n) — one matrix — or (B, k·b, n) — a stack. The batch
dimension stays device-local (in_specs P(None, "servers", None)); the
"servers" axis and the relay schedule are unchanged, so a single
wavefront sweep factors all B matrices: the relay hops are paid once per
batch instead of once per matrix.

The relay buffer is the fixed-shape (n, n) U matrix (rows ≥ t still zero).
The paper's variable-size messages (rows 0..t only) would be a ragged
send; fixed-shape relay overcounts bytes by ≤ 2× — accounted for in
benchmarks (CommLog tracks the paper-exact volume).

The per-chip active computation is gated behind `lax.cond` on the traced
axis index, so passive chips do no FLOPs while the wavefront is
elsewhere — faithful to the paper's staggered activation (§IV.D.3).
The "exact" and "stream" programs run one server per device only.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import AxisType, NamedSharding
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


def _inject_faults(l_row, u_row, my_id, faults, *, n, batched, per_chip=1):
    """Device-output fault injection (core.faults surface, distributed leg).

    The mesh device holding the faulty server corrupts the (B, b, n) strip
    that server reports — tamper modes and dropouts are first-class on the
    real pipeline, not just the single-process simulation. With k =
    `per_chip` servers per chip, l_row / u_row are the chip's (B, k·b, n)
    slabs and server s is slot s % k of chip s // k. Faults are static (part of the compile
    cache key); the injection is a `where` on the traced axis index, so
    honest devices' outputs pass through untouched. In-band relay
    poisoning is NOT modeled here (see core.lu.lu_nserver).
    """
    import numpy as np

    from repro.core.faults import corrupt_strip

    b = l_row.shape[-2] // per_chip
    for f in faults:
        targets = ("l", "u") if f.kind == "dropout" else tuple(f.target)
        chip, slot = divmod(f.server, per_chip)
        rows = slice(slot * b, (slot + 1) * b)

        def masked(orig, factor, f=f, rows=rows):
            strip = orig[:, rows]
            bad = corrupt_strip(strip, f, n=n, factor=factor)
            if f.matrices is not None and batched:
                idx = np.asarray(f.matrices, dtype=np.int32)
                bad = strip.at[idx].set(bad[idx])
            return jnp.where(my_id == chip, orig.at[:, rows].set(bad), orig)

        if "l" in targets:
            l_row = masked(l_row, "l")
        if "u" in targets:
            u_row = masked(u_row, "u")
    return l_row, u_row


def _serve_row(t, x_row, args, *, n: int, b: int):
    """Server t's Alg.-3 row in the relay's operation order: x_row its
    (B, b, n) block row, args = (u_buf, l_row, u_row) with u_buf the
    (B, n, n) relay buffer holding the U rows of servers 0..t-1 (zeros
    below) and t a traced int32. Returns the buffer with server t's U row
    written in (the one-way hand-off to server t+1) and its strips."""
    u_buf, l_row, u_row = args  # (B,n,n), (B,b,n), (B,b,n)
    B = x_row.shape[0]
    zero = jnp.zeros((), jnp.int32)

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

    l_row = lax.fori_loop(0, t, lblk, l_row)

    # --- Schur update of the whole row, blocked-panel LU of the diag ---
    s = x_row - precise_matmul(l_row, u_buf)
    ib = (t * b).astype(jnp.int32)
    sii = lax.dynamic_slice(s, (zero, zero, ib), (B, b, b))
    lii, uii = _factor_diag(sii)
    l_row = lax.dynamic_update_slice(l_row, lii, (zero, zero, ib))

    # --- U_{i,j} for j >= i, vectorized over the full row ---
    r = jax.scipy.linalg.solve_triangular(lii, s, lower=True, unit_diagonal=True)
    cols = lax.broadcasted_iota(jnp.int32, (B, b, n), 2)
    u_row = jnp.where(cols >= ib, r, jnp.zeros_like(r))
    u_buf = lax.dynamic_update_slice(u_buf, u_row, (zero, ib, zero))
    return u_buf, l_row, u_row


def _server_program(x_blk: jnp.ndarray, *, n: int, b: int, num_servers: int,
                    axis: str, faults=()) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Runs on every chip inside shard_map. x_blk: (k·b, n) or (B, k·b, n),
    the block rows of the chip's k = N / chips servers (`per_chip`)."""
    per_chip = x_blk.shape[-2] // b
    chips = num_servers // per_chip
    my_chip = lax.axis_index(axis)
    x_slab, batched = _batched_view(x_blk, per_chip * b, n)
    B = x_slab.shape[0]
    zero = jnp.zeros((), jnp.int32)

    def server_j(j, args):
        """Slot j of the active chip: server my_chip·k + j. The buffer it
        writes is the hand-off to slot j+1 on the same chip."""
        u_buf, l_slab, u_slab = args
        at = (zero, (j * b).astype(jnp.int32), zero)
        strip = (u_buf, lax.dynamic_slice(l_slab, at, (B, b, n)),
                 lax.dynamic_slice(u_slab, at, (B, b, n)))
        x_row = lax.dynamic_slice(x_slab, at, (B, b, n))
        u_buf, l_row, u_row = _serve_row(my_chip * per_chip + j, x_row,
                                         strip, n=n, b=b)
        return (u_buf, lax.dynamic_update_slice(l_slab, l_row, at),
                lax.dynamic_update_slice(u_slab, u_row, at))

    def active(args):
        return lax.fori_loop(0, per_chip, server_j, args)

    def passive(args):
        return args

    fwd = [(i, (i + 1) % chips) for i in range(chips)]

    def round_fn(d, state):
        u_buf, l_row, u_row = state
        u_buf, l_row, u_row = lax.cond(
            my_chip == d, active, passive, (u_buf, l_row, u_row)
        )
        # one-way relay to the next chip (ring hop; only the d -> d+1 edge
        # carries fresh data, matching the paper's single send per phase)
        u_buf = lax.ppermute(u_buf, axis, fwd)
        return u_buf, l_row, u_row

    u_buf0 = jnp.zeros((B, n, n), dtype=x_slab.dtype)
    l_row0 = jnp.zeros((B, per_chip * b, n), dtype=x_slab.dtype)
    u_row0 = jnp.zeros((B, per_chip * b, n), dtype=x_slab.dtype)
    # carries become device-varying inside the loop; mark them so upfront
    u_buf0, l_row0, u_row0 = lax.pcast(
        (u_buf0, l_row0, u_row0), (axis,), to="varying"
    )
    _, l_row, u_row = lax.fori_loop(
        0, chips, round_fn, (u_buf0, l_row0, u_row0)
    )
    if faults:
        l_row, u_row = _inject_faults(l_row, u_row, my_chip, faults, n=n,
                                      batched=batched, per_chip=per_chip)
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


def relay_chips(num_servers: int, devices: int, program: str = "baseline") -> int:
    """Chips the relay of `num_servers` servers runs on, given `devices`:
    the largest divisor of N that is at most the device count, so each
    chip holds k = N / chips consecutive servers. The "exact" and "stream"
    programs run one server per device and refuse N > devices."""
    if program != "baseline":
        if devices < num_servers:
            raise ValueError(
                f"the {program!r} relay program needs one device per server: "
                f"N={num_servers}, but {devices} device(s); the baseline "
                "program runs several servers per chip"
            )
        return num_servers
    return max(d for d in range(1, min(num_servers, devices) + 1)
               if num_servers % d == 0)


@lru_cache(maxsize=None)
def relay_sharding(num_servers: int, batched: bool, axis: str = "servers",
                   program: str = "baseline") -> NamedSharding:
    """Where the relay wants its input: block rows over the first
    `relay_chips` devices of this process (P(axis, None), the batch
    dimension device-local)."""
    chips = relay_chips(num_servers, len(jax.devices()), program)
    mesh = jax.make_mesh((chips,), (axis,), axis_types=(AxisType.Auto,),
                         devices=tuple(jax.devices()[:chips]))
    return NamedSharding(mesh, P(None, axis, None) if batched else P(axis, None))


def _shard_program(mesh, program: str, n: int, batched: bool,
                   num_servers: int, axis: str, faults=()):
    spec = P(None, axis, None) if batched else P(axis, None)
    return jax.jit(jax.shard_map(
        partial(_PROGRAMS[program], n=n, b=n // num_servers,
                num_servers=num_servers, axis=axis, faults=faults),
        mesh=mesh,
        in_specs=spec,
        out_specs=(spec, spec),
    ))


@lru_cache(maxsize=None)
def _compiled_pipeline(program: str, n: int, batch: int | None,
                       num_servers: int, axis: str, faults=()):
    """Build + jit one pipeline program on this process's relay mesh
    (`relay_sharding`).

    Cached so repeated protocol calls (the high-throughput serving path)
    reuse the compiled executable instead of re-tracing a fresh shard_map.
    """
    mesh = relay_sharding(num_servers, batch is not None, axis, program).mesh
    return _shard_program(mesh, program, n, batch is not None, num_servers,
                          axis, faults)


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
    the mesh device holding each faulty server corrupts (or zeroes) the
    strip it reports. Delay faults must be resolved by the caller
    (core.faults.resolve_delays); in-band relay poisoning is only modeled
    by the single-process simulation and is rejected here.

    mesh: optional existing mesh containing `axis`, whose size must divide
    N (k = N / size servers per chip); default: `relay_chips` of this
    process's devices. The "exact" and "stream" programs take one server
    per device.

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
        fn = _compiled_pipeline(program, n, batch, num_servers, axis, faults)
    else:
        chips = mesh.shape[axis]
        if num_servers % chips:
            raise ValueError(
                f"a mesh axis of {chips} device(s) does not divide "
                f"N={num_servers} servers into whole servers per chip"
            )
        relay_chips(num_servers, chips, program)  # exact/stream: k = 1 only
        fn = _shard_program(mesh, program, n, batch is not None, num_servers,
                            axis, faults)
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
