"""The cycle body's dense lookups equal plain jnp indexing.

The controller and the fast-forward horizon read state at a per-slot index
by compare-select-reduce over the small static axis it indexes
(``device.pick``, ``select_row``, ``table_at``, ``lut``), because a gather
under the engine's (batch x channel) vmap nesting serializes on the TPU.
Each case draws tables at a standard's own widths, with ``NEG`` entries,
and in-range indices that include the first and last bank and the last
refresh unit, and compares the dense form with jnp indexing under a vmap
over channels inside a vmap over a batch axis.
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import compile_spec
from repro.core import device as D
from repro.core import spec as S

STANDARDS = {
    "DDR5_2R": ("DDR5", "DDR5_16Gb_x8_2R", "DDR5_4800B"),
    "HBM3": ("HBM3", "HBM3_16Gb", "HBM3_5200"),            # dual command bus
    "LPDDR5_2R": ("LPDDR5", "LPDDR5_8Gb_x16_2R", "LPDDR5_6400"),  # split ACT
    "GDDR7": ("GDDR7", "GDDR7_16Gb_x32", "GDDR7_32"),       # data-clock sync
}
BATCH, CHANNELS, Q = 3, 2, 32
NEG = int(D.NEG)


def _ints(rng, shape, lo=-1000, hi=1 << 20):
    """Random int32 values with about a quarter of them ``NEG``."""
    x = rng.integers(lo, hi, size=shape)
    return np.where(rng.random(shape) < 0.25, NEG, x).astype(np.int32)


def _idx(rng, shape, n):
    """Random indices into ``n`` entries; the first and last always occur."""
    i = rng.integers(0, n, size=shape)
    i[..., 0], i[..., -1] = 0, n - 1
    return i.astype(np.int32)


def _nested(fn):
    """``fn`` vmapped over channels, then over the batch axis."""
    return jax.jit(jax.vmap(jax.vmap(fn)))


def _case(cspec, name, rng):
    """``(dense, plain, args)``: both functions take the same arrays of
    shape ``(BATCH, CHANNELS, ...)``."""
    lead = (BATCH, CHANNELS)
    nb, nru, nc = cspec.n_banks, cspec.n_refresh_units, cspec.n_cmds
    bank, ru = _idx(rng, lead + (Q,), nb), _idx(rng, lead + (Q,), nru)
    cmd = _idx(rng, lead + (Q,), nc)
    slot = rng.integers(0, Q, size=lead).astype(np.int32)
    slot[0, 0], slot[-1, -1] = 0, Q - 1
    if name == "row_state[bank]":           # also act1_row, act1_clk
        return (lambda x, i: D.pick(x, D.onehot(i, nb)),
                lambda x, i: x[i], (_ints(rng, lead + (nb,)), bank))
    if name == "clock_until[ru]":           # also last_ref
        return (lambda x, i: D.pick(x, D.onehot(i, nru)),
                lambda x, i: x[i], (_ints(rng, lead + (nru,)), ru))
    if name == "ref_urgent[ru]":
        return (lambda x, i: D.pick(x, D.onehot(i, nru)),
                lambda x, i: x[i], (rng.random(lead + (nru,)) < 0.5, ru))
    if name == "table[cmd, bank]":
        return (lambda t, c, b: D.table_at(t, c, D.onehot(b, nb)),
                lambda t, c, b: t[c, b],
                (_ints(rng, lead + (nc, nb)), cmd, bank))
    if name == "table[prereq cmd, bank]":
        cmds = D.prereq_cmds(cspec)
        pc = np.asarray(cmds, np.int32)[_idx(rng, lead + (Q,), len(cmds))]
        return (lambda t, c, b: D.table_at(t, c, D.onehot(b, nb), cmds),
                lambda t, c, b: t[c, b],
                (_ints(rng, lead + (nc, nb)), pc, bank))
    if name == "table[ref_cmd, rep]":
        bpr = nb // nru
        ref_cmd = np.where(rng.random(lead + (nru,)) < 0.5, cspec.id_PREab,
                           cspec.id_REFab).astype(np.int32)
        return (lambda t, c: D.select_row(
                    jax.lax.slice(t, (0, 0), t.shape, (1, bpr)), c,
                    (cspec.id_PREab, cspec.id_REFab)),
                lambda t, c: t[c, jnp.arange(nru) * bpr],
                (_ints(rng, lead + (nc, nb)), ref_cmd))
    if name == "sub[slot]":
        sub = np.stack([_idx(rng, lead + (Q,), int(n))
                        for n in cspec.level_counts[1:]], axis=-1)
        return (lambda s, k: D.pick(s, D.onehot(k, Q)),
                lambda s, k: s[k], (sub, slot))
    if name == "arrive[slot]":
        return (lambda x, k: D.pick(x, D.onehot(k, Q)),
                lambda x, k: x[k], (_ints(rng, lead + (Q,)), slot))
    if name == "is_probe[slot]":
        return (lambda x, k: D.pick(x, D.onehot(k, Q)),
                lambda x, k: x[k], (rng.random(lead + (Q,)) < 0.5, slot))
    if name == "cmd_fx[cmd]":
        return (lambda c: D.lut(cspec.cmd_fx, c),
                lambda c: jnp.asarray(cspec.cmd_fx)[c], (cmd,))
    if name == "cmd_scope[cmd]":
        return (lambda c: D.lut(cspec.cmd_scope, c),
                lambda c: jnp.asarray(cspec.cmd_scope)[c], (cmd,))
    if name == "kind_ok[cmd_kind[cmd]]":
        kind_ok = np.asarray([k in (S.KIND_COL, S.KIND_SYNC)
                              for k in range(4)])
        return (lambda c: D.lut(kind_ok[cspec.cmd_kind], c),
                lambda c: jnp.asarray(kind_ok)[jnp.asarray(cspec.cmd_kind)[c]],
                (cmd,))
    raise KeyError(name)


LOOKUPS = ("row_state[bank]", "clock_until[ru]", "ref_urgent[ru]",
           "table[cmd, bank]", "table[prereq cmd, bank]",
           "table[ref_cmd, rep]", "sub[slot]",
           "arrive[slot]", "is_probe[slot]", "cmd_fx[cmd]", "cmd_scope[cmd]",
           "kind_ok[cmd_kind[cmd]]")


@pytest.mark.parametrize("std", sorted(STANDARDS))
@pytest.mark.parametrize("lookup", LOOKUPS)
def test_dense_lookup_equals_indexing(std, lookup):
    cspec = compile_spec(*STANDARDS[std])
    rng = np.random.default_rng(zlib.crc32(f"{std}/{lookup}".encode()))
    dense, plain, args = _case(cspec, lookup, rng)
    got = np.asarray(_nested(dense)(*args))
    want = np.asarray(_nested(plain)(*args))
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got, want)
    if lookup.startswith(("row_state", "clock_until", "table", "arrive")):
        assert (want == NEG).any()          # NEG entries came through


def _prereq_by_gather(cspec, state, is_write, sub, row, clk):
    """The prerequisite decode of one request by plain indexing (the form
    the dense one replaced), vmapped over the queue by the caller."""
    bank = D.flat_bank(cspec, sub)
    rs = state.row_state[bank]
    open_hit = rs == row
    final = jnp.where(is_write, cspec.id_WR, cspec.id_RD)
    col_cmd = final
    if cspec.data_clock_sync:
        sync = jnp.where(
            is_write,
            cspec.id_CAS_WR if cspec.id_CAS_WR >= 0 else cspec.id_RCKSTRT,
            cspec.id_CAS_RD if cspec.id_CAS_RD >= 0 else cspec.id_RCKSTRT)
        col_cmd = jnp.where(clk < state.clock_until[sub[0]], final, sync)
    opener = cspec.id_ACT1 if cspec.split_activation else cspec.id_ACT
    cmd = jnp.where(open_hit, col_cmd, cspec.id_PRE)
    if cspec.split_activation:
        cmd = jnp.where(rs == D.ROW_ACTIVATING, cspec.id_ACT2, cmd)
    cmd = jnp.where(rs == D.ROW_CLOSED, opener, cmd).astype(jnp.int32)
    cmd_row = row
    if cspec.split_activation:
        cmd_row = jnp.where(cmd == cspec.id_ACT2, state.act1_row[bank], row)
    return cmd, cmd_row, open_hit


@pytest.mark.parametrize("std", sorted(STANDARDS))
def test_queue_prereq_equals_per_slot_indexing(std):
    """``D.prereq`` over a whole queue (one-hot reads of ``row_state``,
    ``act1_row`` and ``clock_until``) equals the per-slot decode by
    indexing, in every bank state."""
    cspec = compile_spec(*STANDARDS[std])
    rng = np.random.default_rng(zlib.crc32(std.encode()))
    lead = (BATCH, CHANNELS)
    nb, nru = cspec.n_banks, cspec.n_refresh_units
    clk = jnp.int32(5000)
    base = D.init_state(cspec)
    rows = rng.integers(0, 8, size=lead + (nb,))
    kind = rng.integers(0, 3, size=lead + (nb,))
    row_state = np.where(kind == 0, D.ROW_CLOSED,
                         np.where((kind == 1) & cspec.split_activation,
                                  D.ROW_ACTIVATING, rows)).astype(np.int32)
    states = base._replace(
        row_state=row_state,
        act1_row=rng.integers(0, 8, size=lead + (nb,)).astype(np.int32),
        clock_until=(int(clk) + rng.integers(-4, 4, size=lead + (nru,))
                     ).astype(np.int32),
        last_issue=np.broadcast_to(base.last_issue,
                                   lead + base.last_issue.shape),
        win_ring=np.broadcast_to(base.win_ring, lead + base.win_ring.shape),
        act1_clk=np.broadcast_to(base.act1_clk, lead + (nb,)),
        last_ref=np.broadcast_to(base.last_ref, lead + (nru,)))
    sub = np.stack([_idx(rng, lead + (Q,), int(n))
                    for n in cspec.level_counts[1:]], axis=-1)
    is_write = rng.random(lead + (Q,)) < 0.4
    row = rng.integers(0, 8, size=lead + (Q,)).astype(np.int32)
    dp = D.dyn_params(cspec)

    dense = _nested(lambda st, w, s, r: D.prereq(cspec, dp, st, w, s, r, clk))
    plain = _nested(lambda st, w, s, r: jax.vmap(
        lambda w1, s1, r1: _prereq_by_gather(cspec, st, w1, s1, r1, clk))(
            w, s, r))
    got = dense(states, is_write, sub, row)
    want = plain(states, is_write, sub, row)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    cmds = set(np.asarray(want[0]).ravel().tolist())
    assert {cspec.id_PRE, cspec.id_RD} <= cmds      # several decode paths
    assert cmds <= set(D.prereq_cmds(cspec))
