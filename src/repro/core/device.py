"""Cycle-level DRAM device model as a pure-JAX state machine.

All mutable device state is a pytree of dense int32 arrays; every operation
(prerequisite decode, timing-readiness check, command issue) is a pure
function `(tables, state, ...) -> ...` suitable for `jax.jit`, `jax.vmap`
(DSE batching) and `jax.lax.scan` (the cycle loop).

State encoding
--------------
row_state[bank]  : -1 closed, -2 activating (split ACT-1 issued), else open row
last_issue[node, cmd] : most-recent issue clock — the dense table every
                   window=1 constraint (i.e. almost all of them) reads
win_ring[e, w]   : issue-clock history (most recent first) ONLY for the few
                   (prev_cmd, level) pairs with a window>1 constraint
                   (tFAW's ACT ring); entry layout is planned at spec
                   compile time (``CompiledSpec.ring_*`` / ``ct_ring``)
clock_until[ru]  : WCK/RCK data clock active until this cycle (exclusive)
last_ref[ru]     : last REFab issue clock per refresh unit

Splitting the deep history out of the per-(node, cmd) state shrinks the
``lax.scan`` carry ~4x at DDR5/HBM3 window depths — the whole timing state
is what every cycle of every channel of every batched design point carries,
so its footprint is the engine's cache-pressure knob.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spec as S
from repro.core.compile import CompiledSpec

NEG = jnp.int32(-(1 << 28))     # "never issued"
ROW_CLOSED = -1
ROW_ACTIVATING = -2


class DynParams(NamedTuple):
    """Preset-dependent scalars/vectors — the *vmappable* axis for DSE."""
    ct_lat: jnp.ndarray          # (C,) resolved constraint latencies
    nREFI: jnp.ndarray
    nRFC: jnp.ndarray
    nAAD: jnp.ndarray            # ACT-2 deadline (0 = n/a)
    clock_idle: jnp.ndarray      # WCK/RCK idle window (0 = n/a)
    read_latency: jnp.ndarray    # RD issue -> data valid


def dyn_params(cspec: CompiledSpec) -> DynParams:
    t = cspec.timings
    return DynParams(
        ct_lat=jnp.asarray(cspec.ct_lat, jnp.int32),
        nREFI=jnp.int32(t["nREFI"]), nRFC=jnp.int32(t["nRFC"]),
        nAAD=jnp.int32(cspec.nAAD), clock_idle=jnp.int32(cspec.clock_idle),
        read_latency=jnp.int32(cspec.read_latency),
    )


class DeviceState(NamedTuple):
    last_issue: jnp.ndarray      # (num_nodes, n_cmds) int32 — window=1 table
    win_ring: jnp.ndarray        # (max(n_ring,1), ring_depth) int32
    row_state: jnp.ndarray       # (n_banks,) int32
    act1_row: jnp.ndarray        # (n_banks,) int32
    act1_clk: jnp.ndarray        # (n_banks,) int32
    clock_until: jnp.ndarray     # (n_refresh_units,) int32
    last_ref: jnp.ndarray        # (n_refresh_units,) int32


def init_state(cspec: CompiledSpec) -> DeviceState:
    return DeviceState(
        last_issue=jnp.full((cspec.num_nodes, cspec.n_cmds), NEG, jnp.int32),
        # a standard with no windowed constraints keeps a 1x1 dummy ring so
        # the pytree structure (and gather shapes) stay uniform
        win_ring=jnp.full((max(cspec.n_ring, 1), cspec.ring_depth),
                          NEG, jnp.int32),
        row_state=jnp.full((cspec.n_banks,), ROW_CLOSED, jnp.int32),
        act1_row=jnp.zeros((cspec.n_banks,), jnp.int32),
        act1_clk=jnp.full((cspec.n_banks,), NEG, jnp.int32),
        clock_until=jnp.zeros((cspec.n_refresh_units,), jnp.int32),
        last_ref=jnp.zeros((cspec.n_refresh_units,), jnp.int32),
    )


def carry_nbytes(cspec: CompiledSpec) -> int:
    """Per-channel scan-carry bytes of the timing state (the cache-pressure
    number the windowed-ring split optimizes)."""
    state = init_state(cspec)
    return sum(int(np.prod(a.shape)) * 4
               for a in (state.last_issue, state.win_ring))


def dense_ring_nbytes(cspec: CompiledSpec) -> int:
    """What the pre-split layout — a ``max_window``-deep ring for every
    (node, cmd) pair — would carry.  Kept as the benchmark baseline."""
    return cspec.num_nodes * cspec.n_cmds * cspec.max_window * 4


# --------------------------------------------------------------------------
# Addressing helpers (static org => plain python loops unroll at trace time)
# --------------------------------------------------------------------------

def node_per_level(cspec: CompiledSpec, addr_sub: jnp.ndarray) -> jnp.ndarray:
    """Node index at each hierarchy level for an address.

    addr_sub holds the per-level indices below channel, e.g. DDR4:
    (rank, bankgroup, bank).  Returns (L,) node ids; level 0 is channel 0.
    """
    counts = cspec.level_counts        # numpy, static
    offs = cspec.level_offsets
    nodes = [jnp.int32(0)]
    flat = jnp.int32(0)
    for i in range(1, len(counts)):
        flat = flat * jnp.int32(int(counts[i])) + addr_sub[i - 1]
        nodes.append(jnp.int32(int(offs[i])) + flat)
    return jnp.stack(nodes)


def flat_bank(cspec: CompiledSpec, addr_sub: jnp.ndarray) -> jnp.ndarray:
    """Flat bank id of an address, or of each address along the leading
    axes of ``addr_sub`` (``(..., L-1)``)."""
    counts = cspec.level_counts
    flat = jnp.int32(0)
    for i in range(1, len(counts)):
        flat = flat * jnp.int32(int(counts[i])) + addr_sub[..., i - 1]
    return flat


def refresh_unit(cspec: CompiledSpec, addr_sub: jnp.ndarray) -> jnp.ndarray:
    return addr_sub[..., 0]


# --------------------------------------------------------------------------
# Dense lookups (no gathers)
# --------------------------------------------------------------------------
#
# XLA:TPU lowers a gather under the engine's (batch x channel) vmap nesting
# to a near-serial loop over its elements, so the cycle body reads state at
# a per-slot index by compare-select-reduce over the small static axis the
# index ranges over (banks, refresh units, commands, queue slots).  Each
# helper equals plain indexing for an in-range index; every caller's index
# is in range (it says why), so jnp's index clamp never applies.  The
# indexed axis comes first in a one-hot, so the reductions run down it,
# across vector registers, and not across the lanes of one.

def onehot(idx, n: int) -> jnp.ndarray:
    """``arange(n) == idx`` with the ``n`` axis first: the one-hot of an
    index into a static axis of ``n`` entries, shape ``(n,) + idx.shape``."""
    idx = jnp.asarray(idx)
    return jnp.arange(n, dtype=jnp.int32).reshape((n,) + (1,) * idx.ndim) \
        == idx


def pick(x: jnp.ndarray, hot: jnp.ndarray) -> jnp.ndarray:
    """``x[i]`` given ``hot = onehot(i, n)``, for ``x`` of shape ``(n,) +
    tail``: shape ``i.shape + tail``.  Exactly one entry is selected, so
    the sum (``any`` for bools) is exact and its neutral 0 never meets a
    stored value (``NEG`` entries come through unchanged)."""
    tail = x.shape[1:]
    hot = hot.reshape(hot.shape + (1,) * len(tail))
    x = x.reshape(x.shape[:1] + (1,) * (hot.ndim - 1 - len(tail)) + tail)
    if x.dtype == jnp.bool_:
        return jnp.any(hot & x, axis=0)
    return jnp.sum(jnp.where(hot, x, 0), axis=0, dtype=x.dtype)


def select_row(table: jnp.ndarray, row, rows=None) -> jnp.ndarray:
    """``table[row]``, ``row`` broadcast against ``table.shape[1:]``: a
    select chain over ``rows``, the static row ids ``row`` can hold
    (default: all).  The last of them is not compared, so a ``row`` outside
    ``rows`` reads it."""
    rows = range(table.shape[0]) if rows is None else rows
    *rest, last = [int(r) for r in rows]     # static indices: slices
    shape = jnp.broadcast_shapes(jnp.shape(row), table.shape[1:])
    out = jnp.broadcast_to(table[last], shape)
    for r in rest:
        out = jnp.where(row == r, table[r], out)
    return out


def table_at(table: jnp.ndarray, cmd, bank_hot, cmds=None) -> jnp.ndarray:
    """``table[cmd, bank]`` of the ``(n_cmds, n_banks)`` earliest-issue
    table per element of ``cmd``, given ``bank_hot = onehot(bank,
    n_banks)``: each element's command row (:func:`select_row` over
    ``cmds``, the ids ``cmd`` can hold), then its bank."""
    cmd = jnp.asarray(cmd)
    by_bank = table.reshape(table.shape + (1,) * cmd.ndim)
    rows = select_row(by_bank, cmd, cmds)          # (n_banks,) + cmd.shape
    return jnp.sum(jnp.where(bank_hot, rows, 0), axis=0, dtype=table.dtype)


def lut(values, idx) -> jnp.ndarray:
    """``values[idx]`` for a static numpy table: a select chain over the
    entries that differ from the last (the constant tables indexed by
    command: ``cmd_fx``, ``cmd_scope``, a pass's command-kind mask)."""
    values = np.asarray(values)
    out = jnp.full(jnp.shape(idx), values[-1], values.dtype)
    for c in range(len(values) - 1):
        if values[c] != values[-1]:
            out = jnp.where(idx == c, values[c], out)
    return out


# --------------------------------------------------------------------------
# Timing-readiness check (XLA reference path; Pallas kernel in kernels/)
# --------------------------------------------------------------------------

def earliest_ready(cspec: CompiledSpec, dp: DynParams, state: DeviceState,
                   cmd: jnp.ndarray, addr_sub: jnp.ndarray) -> jnp.ndarray:
    """Earliest cycle at which `cmd` may issue at `addr` (timing only)."""
    nodes = node_per_level(cspec, addr_sub)          # (L,)
    ct_prev = jnp.asarray(cspec.ct_prev)             # (C,)
    ct_next = jnp.asarray(cspec.ct_next)
    ct_level = jnp.asarray(cspec.ct_level)
    node = nodes[ct_level]                           # (C,)
    t_prev = state.last_issue[node, ct_prev]
    if cspec.n_ring:
        # windowed rows read the pair's ring entry for this level node;
        # rows with ct_ring == -1 (window=1, or a window the command never
        # stamps) keep the dense-table value / NEG
        ct_ring = jnp.asarray(cspec.ct_ring)
        lvl_off = jnp.asarray(np.asarray(cspec.level_offsets,
                                         np.int32)[cspec.ct_level])
        ridx = jnp.clip(ct_ring + node - lvl_off, 0, cspec.n_ring - 1)
        t_ring = state.win_ring[ridx, jnp.asarray(cspec.ct_win) - 1]
        t_prev = jnp.where(ct_ring >= 0, t_ring, t_prev)
    # window>1 rows at a level the command never stamps have ct_ring == -1
    # AND a never-written dense slot, so they correctly stay NEG
    allowed = jnp.where((ct_next == cmd) & (t_prev > NEG),
                        t_prev + dp.ct_lat, NEG)
    return jnp.max(allowed, initial=NEG)


def earliest_ready_table(cspec: CompiledSpec, dp: DynParams,
                         state: DeviceState) -> jnp.ndarray:
    """Dense ``(n_cmds, n_banks)`` earliest-issue table for the whole
    device — the vectorized twin of :func:`earliest_ready`.

    The constraint table is static, so the whole computation unrolls at
    trace time into static slices: each constraint row reads its level's
    node timestamps with a static slice of ``last_issue`` and broadcasts
    them to banks with a static ``repeat`` — no gathers or scatters at
    all (dynamic gathers serialize under nested vmap on CPU/TPU).  The
    controller reads a queue slot's entry with :func:`table_at`, a dense
    select over the table's commands and banks: a ``table[cmd, bank]``
    gather per slot would serialize the same way, and on the TPU it cost
    more than building the table.
    """
    n_banks = cspec.n_banks
    sizes = np.asarray(cspec.level_counts, np.int64)
    node_counts = np.cumprod(sizes)                  # nodes per level
    offs = np.asarray(cspec.level_offsets, np.int64)
    acc = [None] * cspec.n_cmds                      # per-cmd running max
    for i in range(len(cspec.ct_prev)):
        p, f = int(cspec.ct_prev[i]), int(cspec.ct_next[i])
        level, w = int(cspec.ct_level[i]), int(cspec.ct_win[i]) - 1
        if level > int(cspec.cmd_scope[p]):
            continue        # preceding command never stamps this level
        n_l = int(node_counts[level])
        off = int(offs[level])
        if w == 0:
            # static slice of the dense table: the level's nodes for prev
            t_nodes = state.last_issue[off:off + n_l, p]         # (n_l,)
        else:
            # windowed constraint: the pair's contiguous ring block holds
            # exactly this level's nodes, so the read stays a static slice
            ro = int(cspec.ct_ring[i])
            assert ro >= 0, "reachable window>1 constraint without a ring"
            t_nodes = state.win_ring[ro:ro + n_l, w]             # (n_l,)
        t_banks = jnp.repeat(t_nodes, n_banks // n_l)            # (n_banks,)
        allowed = jnp.where(t_banks > NEG, t_banks + dp.ct_lat[i], NEG)
        acc[f] = allowed if acc[f] is None else jnp.maximum(acc[f], allowed)
    neg_row = jnp.full((n_banks,), NEG, jnp.int32)
    return jnp.stack([a if a is not None else neg_row for a in acc])


def timing_ok(cspec, dp, state, cmd, addr_sub, clk) -> jnp.ndarray:
    return clk >= earliest_ready(cspec, dp, state, cmd, addr_sub)


# --------------------------------------------------------------------------
# Prerequisite decode (paper §2: per-standard request -> next command)
# --------------------------------------------------------------------------

def prereq_cmds(cspec: CompiledSpec) -> tuple:
    """The command ids :func:`prereq` can return."""
    ids = {cspec.id_ACT1 if cspec.split_activation else cspec.id_ACT,
           cspec.id_PRE, cspec.id_RD, cspec.id_WR}
    if cspec.split_activation:
        ids.add(cspec.id_ACT2)
    if cspec.data_clock_sync:
        ids |= {c for c in (cspec.id_CAS_RD, cspec.id_CAS_WR,
                            cspec.id_RCKSTRT) if c >= 0}
    return tuple(sorted(ids))


def prereq(cspec: CompiledSpec, dp: DynParams, state: DeviceState,
           is_write: jnp.ndarray, addr_sub: jnp.ndarray, row: jnp.ndarray,
           clk: jnp.ndarray):
    """Next command needed to advance a request, or each request along the
    leading axes of ``is_write``, ``addr_sub`` (``(..., L-1)``) and ``row``
    (the controller passes its whole queue).

    Returns (cmd, cmd_row, open_hit): cmd_row is the row the command
    actually targets (ACT-2 completes the *pending* activation row, not the
    request's row).  The bank and refresh-unit state reads are one-hot
    selects (:func:`pick`); an address's indices lie inside the
    organisation (the mapper decodes them so, and an empty queue slot holds
    zeros), so its bank and refresh unit are in range.
    """
    bank_hot = onehot(flat_bank(cspec, addr_sub), cspec.n_banks)
    rs = pick(state.row_state, bank_hot)
    open_hit = rs == row
    closed = rs == ROW_CLOSED

    final = jnp.where(is_write, jnp.int32(cspec.id_WR), jnp.int32(cspec.id_RD))
    col_cmd = final
    if cspec.data_clock_sync:
        with jax.named_scope("clock_sync"):
            ru_hot = onehot(refresh_unit(cspec, addr_sub),
                            cspec.n_refresh_units)
            clock_on = clk < pick(state.clock_until, ru_hot)
            sync = jnp.where(is_write,
                             jnp.int32(cspec.id_CAS_WR if cspec.id_CAS_WR >= 0 else cspec.id_RCKSTRT),
                             jnp.int32(cspec.id_CAS_RD if cspec.id_CAS_RD >= 0 else cspec.id_RCKSTRT))
            col_cmd = jnp.where(clock_on, final, sync)

    cmd_row = row
    if cspec.split_activation:
        with jax.named_scope("split_act"):
            activating = rs == ROW_ACTIVATING
            opener = jnp.int32(cspec.id_ACT1)
            cmd = jnp.where(closed, opener,
                  jnp.where(activating, jnp.int32(cspec.id_ACT2),
                  jnp.where(open_hit, col_cmd, jnp.int32(cspec.id_PRE))))
            cmd_row = jnp.where(cmd == jnp.int32(cspec.id_ACT2),
                                pick(state.act1_row, bank_hot), row)
    else:
        opener = jnp.int32(cspec.id_ACT)
        cmd = jnp.where(closed, opener,
              jnp.where(open_hit, col_cmd, jnp.int32(cspec.id_PRE)))
    return cmd, cmd_row, open_hit


# --------------------------------------------------------------------------
# Command issue: timestamp rings + state effects
# --------------------------------------------------------------------------

def issue(cspec: CompiledSpec, dp: DynParams, state: DeviceState,
          cmd: jnp.ndarray, addr_sub: jnp.ndarray, row: jnp.ndarray,
          clk: jnp.ndarray, enable: jnp.ndarray) -> DeviceState:
    """Issue `cmd` at `addr` on cycle `clk` (no-op when ``enable`` is False).

    Every state mutation is a *dense one-hot masked update* (compare +
    select over the full array) instead of a scatter: scatters serialize
    under the engine's (batch x channel) vmap nesting on CPU/TPU backends,
    while these elementwise forms vectorize across all batch dimensions.
    The arrays are small (nodes x cmds, plus the tiny windowed ring), so
    the extra flops are noise next to the removed gather/scatter loops.
    The constant per-command tables are read the same way (:func:`lut`);
    ``cmd`` is a command id of the standard.
    """
    nodes = node_per_level(cspec, addr_sub)                    # (L,)
    scope = lut(cspec.cmd_scope, cmd)
    lvl_idx = jnp.arange(len(cspec.levels), dtype=jnp.int32)
    upd_mask = (lvl_idx <= scope) & enable                     # ancestors+self

    li = state.last_issue                                      # (N, cmds)
    node_ids = jnp.arange(cspec.num_nodes, dtype=jnp.int32)
    node_hit = jnp.any((node_ids[:, None] == nodes[None, :])
                       & upd_mask[None, :], axis=1)            # (N,)
    cmd_hit = jnp.arange(cspec.n_cmds, dtype=jnp.int32) == cmd  # (cmds,)
    li = jnp.where(node_hit[:, None] & cmd_hit[None, :], clk, li)

    ring = state.win_ring
    if cspec.n_ring:
        # shift-insert only the ring entries owned by (cmd, its level node);
        # a ring pair exists only for levels the command stamps, so the
        # scope mask is implied by ring_cmd == cmd
        r_cmd = jnp.asarray(cspec.ring_cmd)
        r_node = jnp.asarray(cspec.ring_node)
        r_nodes = jnp.stack([nodes[int(lv)] for lv in cspec.ring_level])
        entry_hit = (r_cmd == cmd) & (r_nodes == r_node) & enable
        shifted = jnp.concatenate(
            [jnp.full_like(ring[:, :1], clk), ring[:, :-1]], axis=1)
        ring = jnp.where(entry_hit[:, None], shifted, ring)

    fx = lut(cspec.cmd_fx, cmd)
    bank = flat_bank(cspec, addr_sub)
    ru = refresh_unit(cspec, addr_sub)
    bank_hit = jnp.arange(cspec.n_banks, dtype=jnp.int32) == bank
    ru_hit = jnp.arange(cspec.n_refresh_units, dtype=jnp.int32) == ru

    def has(bit):
        return ((fx & bit) != 0) & enable

    rs = state.row_state
    rs = jnp.where(has(S.FX_OPEN) & bank_hit, row, rs)
    rs = jnp.where(has(S.FX_CLOSE) & bank_hit, ROW_CLOSED, rs)
    # FX_CLOSE_ALL: close every bank in this refresh unit
    banks_per_ru = cspec.n_banks // cspec.n_refresh_units
    bank_ru = jnp.arange(cspec.n_banks, dtype=jnp.int32) // banks_per_ru
    rs = jnp.where(has(S.FX_CLOSE_ALL) & (bank_ru == ru), ROW_CLOSED, rs)
    rs = jnp.where(has(S.FX_ACT1) & bank_hit, ROW_ACTIVATING, rs)

    a1_hit = has(S.FX_ACT1) & bank_hit
    a1r = jnp.where(a1_hit, row, state.act1_row)
    a1c = jnp.where(a1_hit, clk, state.act1_clk)

    cu = state.clock_until
    if cspec.data_clock_sync:
        with jax.named_scope("clock_sync"):
            cu = jnp.where(has(S.FX_CLOCK_ON) & ru_hit,
                           clk + dp.clock_idle, cu)
            # data transfer keeps the data clock alive
            is_data = has(S.FX_FINAL_RD) | has(S.FX_FINAL_WR)
            cu = jnp.where(is_data & ru_hit,
                           jnp.maximum(cu, clk + dp.clock_idle), cu)

    lr = state.last_ref
    lr = jnp.where((cmd == jnp.int32(cspec.id_REFab)) & enable & ru_hit,
                   clk, lr)

    return DeviceState(last_issue=li, win_ring=ring, row_state=rs,
                       act1_row=a1r, act1_clk=a1c, clock_until=cu,
                       last_ref=lr)
