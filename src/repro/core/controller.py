"""Memory-controller base scheduling workflow + filtering predicates.

This is the paper's central software-architecture contribution (§2): one
*common* command-selection pipeline that every controller specializes by
injecting *filtering predicates* — composable functions producing boolean
masks over the request queue:

  * HBM3/4, GDDR7 dual C/A bus  -> run the pipeline twice per cycle with a
    column-command mask then a row-command mask;
  * LPDDR5/6 split activation   -> predicates that (a) let only requests
    that already issued ACT-1 proceed to ACT-2 and (b) prioritize a pending
    ACT-2 as its tAAD deadline approaches;
  * WCK/RCK data-clock sync     -> the prerequisite decoder injects
    CAS_RD/CAS_WR/RCKSTRT before column commands when the clock is off;
  * BlockHammer                 -> defer ACTs to blacklisted (hammered) rows;
  * PRAC                        -> alert-driven recovery (RFM) that ordinary
    requests must not interfere with.

All of it is vectorized: a predicate is `(PredCtx) -> bool[Q]`, and every
per-slot read of state or of a constant table is a dense one-hot select
(``device.pick`` / ``table_at`` / ``lut``), not a gather; only the
BlockHammer sketch and PRAC alert lookups, off by default, still index.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import device as D
from repro.core import spec as S
from repro.core.compile import CompiledSpec

# --------------------------------------------------------------------------
# Request schedulers: masked-priority selection over the request queue
# --------------------------------------------------------------------------
#
# A scheduler is a pure function `(mask, row_hit, arrive) -> (slot, ok)` that
# picks at most one queue slot among those allowed by `mask`.  The paper's
# base workflow runs the *same* selection pipeline for every controller; the
# controllers differ only in the predicate masks they inject (paper §2).

I32_MAX = jnp.int32(2**31 - 1)


def _oldest(mask, arrive):
    key = jnp.where(mask, arrive, I32_MAX)
    return jnp.argmin(key), jnp.any(mask)


def frfcfs(mask, row_hit, arrive):
    """First-Ready FCFS: ready row hits first, then oldest ready."""
    hit_mask = mask & row_hit
    use_hits = jnp.any(hit_mask)
    m = jnp.where(use_hits, hit_mask, mask)
    return _oldest(m, arrive)


def fcfs(mask, row_hit, arrive):
    return _oldest(mask, arrive)


SCHEDULERS = {"FRFCFS": frfcfs, "FCFS": fcfs}

# --------------------------------------------------------------------------
# Queue / controller state
# --------------------------------------------------------------------------


class Queue(NamedTuple):
    valid: jnp.ndarray      # (Q,) bool
    is_write: jnp.ndarray   # (Q,) bool
    is_probe: jnp.ndarray   # (Q,) bool
    sub: jnp.ndarray        # (Q, L-1) per-level indices below channel
    row: jnp.ndarray        # (Q,)
    col: jnp.ndarray        # (Q,)
    arrive: jnp.ndarray     # (Q,)


def empty_queue(cspec: CompiledSpec, depth: int) -> Queue:
    nsub = len(cspec.levels) - 1
    z = lambda *sh: jnp.zeros(sh, jnp.int32)
    return Queue(valid=jnp.zeros((depth,), bool),
                 is_write=jnp.zeros((depth,), bool),
                 is_probe=jnp.zeros((depth,), bool),
                 sub=z(depth, nsub), row=z(depth), col=z(depth),
                 arrive=z(depth))


def queue_insert(q: Queue, is_write, is_probe, sub, row, col, arrive, want):
    """Insert one request into the first free slot (returns (q', ok)).

    Dense one-hot update (no scatter) — vectorizes under the engine's
    channel/batch vmap nesting."""
    free = ~q.valid
    ok = want & jnp.any(free)
    slot = jnp.argmax(free)          # first free slot
    hit = ok & (jnp.arange(q.valid.shape[0], dtype=jnp.int32) == slot)
    def put(a, v):
        return jnp.where(hit, v, a)
    return Queue(valid=q.valid | hit,
                 is_write=put(q.is_write, is_write),
                 is_probe=put(q.is_probe, is_probe),
                 sub=jnp.where(hit[:, None], sub[None, :], q.sub),
                 row=put(q.row, row), col=put(q.col, col),
                 arrive=put(q.arrive, arrive)), ok


class CtrlState(NamedTuple):
    dev: D.DeviceState
    queue: Queue
    hit_streak: jnp.ndarray   # (n_banks,) consecutive row-hit services
    bh_sketch: jnp.ndarray    # (2, SKETCH) BlockHammer count-min sketch
    prac_count: jnp.ndarray   # (n_banks,) ACT counter since last recovery


SKETCH = 1024


def init_ctrl_state(cspec: CompiledSpec, depth: int) -> CtrlState:
    return CtrlState(dev=D.init_state(cspec),
                     queue=empty_queue(cspec, depth),
                     hit_streak=jnp.zeros((cspec.n_banks,), jnp.int32),
                     bh_sketch=jnp.zeros((2, SKETCH), jnp.int32),
                     prac_count=jnp.zeros((cspec.n_banks,), jnp.int32))


class PredCtx(NamedTuple):
    """Everything a filtering predicate may look at."""
    dp: D.DynParams
    cs: CtrlState
    clk: jnp.ndarray
    cand_cmd: jnp.ndarray     # (Q,) candidate command per slot
    cand_row: jnp.ndarray     # (Q,)
    open_hit: jnp.ndarray     # (Q,) request's row is open
    bank: jnp.ndarray         # (Q,) flat bank ids
    ru: jnp.ndarray           # (Q,) refresh-unit ids
    ref_urgent: jnp.ndarray   # (n_refresh_units,) refresh must go first
    bank_hot: jnp.ndarray     # (n_banks, Q) one-hot of ``bank`` (D.onehot)
    ru_hot: jnp.ndarray       # (n_refresh_units, Q) one-hot of ``ru``


Predicate = Callable[..., jnp.ndarray]   # (cspec, ctx) -> bool[Q]

# --------------------------------------------------------------------------
# Built-in filtering predicates (paper §2)
# --------------------------------------------------------------------------


def pred_refresh_urgency(cspec, ctx):
    """Block requests to a refresh unit whose refresh is overdue-urgent."""
    return ~D.pick(ctx.ref_urgent, ctx.ru_hot)


def pred_act2_exclusive(cspec, ctx):
    """LPDDR5/6: when a pending ACT-2 approaches its tAAD deadline, only
    ACT-2 candidates may issue (nothing may interrupt it)."""
    if not cspec.split_activation:
        return jnp.ones_like(ctx.cand_cmd, bool)
    with jax.named_scope("split_act"):
        pending = D.pick(ctx.cs.dev.row_state,
                         ctx.bank_hot) == D.ROW_ACTIVATING
        deadline = D.pick(ctx.cs.dev.act1_clk, ctx.bank_hot) + ctx.dp.nAAD
        urgent = pending & (ctx.clk + 2 >= deadline)   # slack of one slot
        is_act2 = ctx.cand_cmd == jnp.int32(cspec.id_ACT2)
        return jnp.where(jnp.any(urgent), is_act2 & urgent, True)


def pred_act2_follows_act1(cspec, ctx):
    """LPDDR5/6: only a request whose bank is Activating may issue ACT-2
    (the prerequisite decoder guarantees it targets the pending row)."""
    if not cspec.split_activation:
        return jnp.ones_like(ctx.cand_cmd, bool)
    with jax.named_scope("split_act"):
        is_act2 = ctx.cand_cmd == jnp.int32(cspec.id_ACT2)
        activating = D.pick(ctx.cs.dev.row_state,
                            ctx.bank_hot) == D.ROW_ACTIVATING
        return ~is_act2 | activating


def _bh_hashes(bank, row):
    k = (bank.astype(jnp.uint32) * jnp.uint32(1_000_003)
         + row.astype(jnp.uint32))
    h0 = ((k * jnp.uint32(2654435761)) >> jnp.uint32(5)) % jnp.uint32(SKETCH)
    h1 = (k * jnp.uint32(40503) + jnp.uint32(2057)) % jnp.uint32(SKETCH)
    return h0.astype(jnp.int32), h1.astype(jnp.int32)


def make_pred_blockhammer(threshold: int):
    """BlockHammer [65]: defer ACTs to rows whose estimated activation count
    exceeds the blacklist threshold."""
    def pred(cspec, ctx):
        opener = cspec.id_ACT1 if cspec.split_activation else cspec.id_ACT
        is_open_cmd = ctx.cand_cmd == jnp.int32(opener)
        h0, h1 = _bh_hashes(ctx.bank, ctx.cand_row)
        est = jnp.minimum(ctx.cs.bh_sketch[0, h0], ctx.cs.bh_sketch[1, h1])
        return ~(is_open_cmd & (est >= threshold))
    return pred


def make_pred_prac(threshold: int):
    """PRAC [66-68]: once a bank's activation counter crosses the alert
    threshold, ordinary requests to its refresh unit are blocked until the
    recovery (RFM, modeled as a priority REFab) completes."""
    def pred(cspec, ctx):
        banks_per_ru = cspec.n_banks // cspec.n_refresh_units
        per_bank_alert = ctx.cs.prac_count >= threshold
        ru_alert = jnp.max(per_bank_alert.reshape(cspec.n_refresh_units,
                                                  banks_per_ru), axis=1)
        return ~ru_alert[ctx.ru]
    return pred


PREDICATES = {
    "refresh_urgency": lambda cspec, ctx: pred_refresh_urgency(cspec, ctx),
    "act2_exclusive": pred_act2_exclusive,
    "act2_follows_act1": pred_act2_follows_act1,
}

# --------------------------------------------------------------------------
# Controller configuration
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ControllerConfig:
    scheduler: str = "FRFCFS"
    queue_depth: int = 32
    refresh_enabled: bool = True
    # urgency margin: refresh becomes *blocking* this many cycles past due
    refresh_urgent_margin: int = 4
    # stagger the initial refresh phase across channels (offset c*nREFI/C,
    # as real controllers do) so an all-channel REF never lands on one
    # cycle; False reproduces the historical in-phase behavior
    refresh_stagger: bool = True
    blockhammer_threshold: int = 0     # 0 = disabled
    prac_threshold: int = 0            # 0 = disabled
    extra_predicates: tuple = ()       # user predicates (cspec, ctx)->bool[Q]

    def predicates(self) -> tuple:
        preds = [pred_refresh_urgency, pred_act2_follows_act1,
                 pred_act2_exclusive]
        if self.blockhammer_threshold:
            preds.append(make_pred_blockhammer(self.blockhammer_threshold))
        if self.prac_threshold:
            preds.append(make_pred_prac(self.prac_threshold))
        return tuple(preds) + tuple(self.extra_predicates)


class StepEvents(NamedTuple):
    """What happened this cycle (static shape; -1 == nothing).

    ``bank`` is the flat bank id of queue-issued commands; refresh-engine
    commands (REFab/PREab) carry the *representative* bank of their refresh
    unit (``ru * banks_per_ru``) so trace auditing can attribute them to the
    right hierarchy node.  ``arrive`` is the served request's arrival clock
    (-1 for refresh-engine commands); ``hit_ready`` records whether a
    post-predicate row-hit candidate existed when this bus slot selected —
    the observable the FR-FCFS row-hit-first audit replays.

    ``slot_ready_at`` and ``ref_ready_at`` are what the (last) selection
    pass looked up before it chose: each queue slot's earliest issue clock
    of its prerequisite command, and each refresh unit's of its refresh
    command.  On a cycle that issued nothing they still hold for the
    post-cycle state, and the engine's fast-forward horizon reads them
    (:func:`idle_horizon`).
    """
    cmd: jnp.ndarray          # (2,) issued command per bus slot [col, row]
    bank: jnp.ndarray         # (2,)
    row: jnp.ndarray          # (2,)
    arrive: jnp.ndarray       # (2,) arrival clk of the served request, -1 n/a
    hit_ready: jnp.ndarray    # (2,) bool — a maskable row-hit was available
    served_read: jnp.ndarray      # bool — a read's final RD issued
    served_write: jnp.ndarray     # bool
    served_probe: jnp.ndarray     # bool — the read served was a probe
    probe_latency: jnp.ndarray    # i32 completion - arrival (valid if probe)
    probe_completion: jnp.ndarray  # i32 absolute completion clock
    deferred: jnp.ndarray         # i32 candidates masked by predicates
    slot_ready_at: jnp.ndarray    # (Q,) earliest issue clock per slot
    ref_ready_at: jnp.ndarray     # (n_refresh_units,) same, refresh cmd


# --------------------------------------------------------------------------
# The base scheduling workflow (paper §2) — one pipeline, many controllers
# --------------------------------------------------------------------------


def _candidates(cspec, dp, cs, clk, bank_hot):
    """Per queue slot: the prerequisite command, its row, whether the
    request's row is open, and the command's earliest issue clock; plus
    the dense table that clock was read from."""
    q = cs.queue
    cand_cmd, cand_row, open_hit = D.prereq(cspec, dp, cs.dev, q.is_write,
                                            q.sub, q.row, clk)
    # dense (n_cmds, n_banks) earliest table, read per slot by a one-hot
    # select (cand_cmd is one of D.prereq_cmds; the bank is in range)
    table = D.earliest_ready_table(cspec, dp, cs.dev)
    t_slot = D.table_at(table, cand_cmd, bank_hot, D.prereq_cmds(cspec))
    return cand_cmd, cand_row, open_hit, t_slot, table


def _refresh_plan(cspec, dp, cs, clk, cfg: ControllerConfig):
    """Per-refresh-unit refresh state: due / urgent / candidate command."""
    dev = cs.dev
    due_time = (clk - dev.last_ref) >= dp.nREFI
    # PRAC recovery requests ride the refresh engine (priority REFab)
    if cfg.prac_threshold:
        banks_per_ru = cspec.n_banks // cspec.n_refresh_units
        alert = jnp.max((cs.prac_count >= cfg.prac_threshold).reshape(
            cspec.n_refresh_units, banks_per_ru), axis=1)
        due = due_time | alert
    else:
        due = due_time
    urgent = (clk - dev.last_ref) >= (dp.nREFI + cfg.refresh_urgent_margin)
    if cfg.prac_threshold:
        urgent = urgent | (due & ~due_time)    # PRAC alerts are always urgent
    urgent = urgent & due
    if not cfg.refresh_enabled:
        due = jnp.zeros_like(due)
        urgent = jnp.zeros_like(urgent)
    return due, urgent, _refresh_cmd(cspec, dev)


def _refresh_cmd(cspec, dev):
    """Per refresh unit, the refresh engine's next command: PREab while any
    of its banks is open, else REFab."""
    banks_per_ru = cspec.n_banks // cspec.n_refresh_units
    any_open = jnp.any(
        dev.row_state.reshape(cspec.n_refresh_units, banks_per_ru)
        != D.ROW_CLOSED, axis=1)
    return jnp.where(any_open, jnp.int32(cspec.id_PREab),
                     jnp.int32(cspec.id_REFab))


def _refresh_ready_at(cspec, table, ref_cmd):
    """Per refresh unit ``u``, ``table[ref_cmd[u], u * banks_per_ru]``: the
    earliest issue clock of its refresh command (PREab or REFab) at its
    representative bank."""
    banks_per_ru = cspec.n_banks // cspec.n_refresh_units
    rep = jax.lax.slice(table, (0, 0), table.shape, (1, banks_per_ru))
    return D.select_row(rep, ref_cmd, (cspec.id_PREab, cspec.id_REFab))


def _ru_addr(cspec, ru):
    """Address-vector stand-in for a refresh-unit-scoped command."""
    nsub = len(cspec.levels) - 1
    return jnp.where(jnp.arange(nsub) == 0, ru, 0).astype(jnp.int32)


def _try_issue_refresh(cspec, dp, cs, clk, due, urgent, ref_cmd,
                       cmd_ok, ref_at):
    """Issue the refresh-engine command of the most-overdue due unit.

    Refresh is *opportunistic* until urgent: a merely-due refresh yields to
    pending requests targeting the same unit; an urgent one preempts (the
    ``refresh_urgency`` predicate blocks those requests at the same time).
    ``ref_at`` is each unit's earliest issue clock of ``ref_cmd``
    (:func:`_refresh_ready_at` of the pass's table).  ``cmd_ok`` is the
    pass's static per-command bus mask.  ``ru`` is an argmax over the
    refresh units, so its one-hot reads are in range.
    """
    score = jnp.where(due, clk - cs.dev.last_ref, -1)
    ru = jnp.argmax(score)
    ru_hot = D.onehot(ru, cspec.n_refresh_units)
    cmd = D.pick(ref_cmd, ru_hot)
    sub = _ru_addr(cspec, ru)
    ok_kind = D.lut(cmd_ok, cmd)
    ready = clk >= D.pick(ref_at, ru_hot)
    q = cs.queue
    pending_here = jnp.any(q.valid & (q.sub[:, 0] == ru))
    may_go = D.pick(urgent, ru_hot) | ~pending_here
    do = jnp.any(due) & ready & ok_kind & may_go
    dev = D.issue(cspec, dp, cs.dev, cmd, sub, jnp.int32(0), clk, do)
    # PRAC: recovery resets the unit's activation counters
    banks_per_ru = cspec.n_banks // cspec.n_refresh_units
    bank_ru = jnp.arange(cspec.n_banks, dtype=jnp.int32) // banks_per_ru
    is_ref = do & (cmd == jnp.int32(cspec.id_REFab))
    prac = jnp.where(is_ref & (bank_ru == ru), 0, cs.prac_count)
    ref_bank = (ru * jnp.int32(banks_per_ru)).astype(jnp.int32)
    return cs._replace(dev=dev, prac_count=prac), do, cmd, ref_bank


def _select_and_issue(cspec, dp, cs, clk, cfg, preds, kind_ok, sched_fn,
                      link_latency: int = 0):
    """One pass of the base pipeline restricted to commands with
    kind_ok[kind] == True (a static mask; dual C/A runs this twice, paper
    §2).

    ``link_latency`` (static, cycles) models a CXL-style link in front of
    this channel: a request is not visible to the controller until
    ``arrive + link_latency``, and read data takes another
    ``link_latency`` cycles to cross back — probe completions therefore
    carry ``2 * link_latency`` of round-trip link time end to end."""
    q = cs.queue
    bank = D.flat_bank(cspec, q.sub)
    ru = D.refresh_unit(cspec, q.sub)
    bank_hot = D.onehot(bank, cspec.n_banks)
    cand_cmd, cand_row, open_hit, t_slot, table = _candidates(
        cspec, dp, cs, clk, bank_hot)
    timing_ready = clk >= t_slot

    due, urgent, ref_cmd = _refresh_plan(cspec, dp, cs, clk, cfg)
    ref_at = _refresh_ready_at(cspec, table, ref_cmd)
    ctx = PredCtx(dp=dp, cs=cs, clk=clk, cand_cmd=cand_cmd,
                  cand_row=cand_row, open_hit=open_hit, bank=bank, ru=ru,
                  ref_urgent=urgent, bank_hot=bank_hot,
                  ru_hot=D.onehot(ru, cspec.n_refresh_units))

    cmd_ok = np.asarray(kind_ok)[cspec.cmd_kind]     # static, per command
    cand_kind_ok = D.lut(cmd_ok, cand_cmd)

    mask = q.valid & timing_ready & cand_kind_ok
    if link_latency:
        # enqueue-boundary link latency: the request only becomes a
        # candidate once it has crossed the link (clk >= arrive + L);
        # zero-link groups skip the op entirely, keeping their traced
        # program — and command streams — bit-identical
        mask = mask & (clk >= q.arrive + jnp.int32(link_latency))
    pre_pred = mask
    for p in preds:
        mask = mask & p(cspec, ctx)
    deferred = jnp.sum(pre_pred & ~mask)

    # refresh engine first (its commands obey the same kind restriction)
    cs, ref_issued, ref_cmd_done, ref_bank = _try_issue_refresh(
        cspec, dp, cs, clk, due, urgent, ref_cmd, cmd_ok, ref_at)

    hit_ready = jnp.any(mask & open_hit) & ~ref_issued
    slot, ok = sched_fn(mask & ~ref_issued, open_hit, q.arrive)
    do = ok & ~ref_issued

    # the chosen slot's fields, read through its one-hot (slot is an
    # argmin over the queue, so in range)
    slot_hit = D.onehot(slot, q.valid.shape[0])
    cmd = D.pick(cand_cmd, slot_hit)
    sub = D.pick(q.sub, slot_hit)
    rowv = D.pick(cand_row, slot_hit)
    arrive = D.pick(q.arrive, slot_hit)
    dev = D.issue(cspec, dp, cs.dev, cmd, sub, rowv, clk, do)

    fx = D.lut(cspec.cmd_fx, cmd)
    fin_rd = do & ((fx & S.FX_FINAL_RD) != 0)
    fin_wr = do & ((fx & S.FX_FINAL_WR) != 0)
    served = fin_rd | fin_wr
    valid = q.valid & ~(slot_hit & served)

    # row-hit streak bookkeeping (FRFCFS-Cap support)
    b = D.pick(bank, slot_hit)
    b_hit = jnp.arange(cspec.n_banks, dtype=jnp.int32) == b
    streak = cs.hit_streak
    streak = jnp.where(served & b_hit, streak + 1, streak)
    opener = cspec.id_ACT1 if cspec.split_activation else cspec.id_ACT
    streak = jnp.where(do & (cmd == jnp.int32(opener)) & b_hit,
                       0, streak)

    # BlockHammer sketch update on row-open
    sk = cs.bh_sketch
    if cfg.blockhammer_threshold:
        h0, h1 = _bh_hashes(b, rowv)
        is_open_cmd = do & (cmd == jnp.int32(opener))
        sk = jnp.where(is_open_cmd,
                       sk.at[0, h0].add(1).at[1, h1].add(1), sk)
        sk = jnp.where(clk % jnp.int32(dp.nREFI) == 0, sk // 2, sk)
    prac = cs.prac_count
    if cfg.prac_threshold:
        is_open_cmd = do & (cmd == jnp.int32(opener))
        prac = jnp.where(is_open_cmd & b_hit, prac + 1, prac)

    probe = fin_rd & D.pick(q.is_probe, slot_hit)
    completion = clk + dp.read_latency
    if link_latency:
        # completion-boundary link latency: the data crosses the link back
        completion = completion + jnp.int32(link_latency)
    ev = dict(
        cmd=jnp.where(do, cmd,
                      jnp.where(ref_issued, ref_cmd_done, jnp.int32(-1))),
        bank=jnp.where(do, b,
                       jnp.where(ref_issued, ref_bank, jnp.int32(-1))),
        row=jnp.where(do, rowv, jnp.int32(-1)),
        arrive=jnp.where(do, arrive, jnp.int32(-1)),
        hit_ready=hit_ready,
        served_read=fin_rd, served_write=fin_wr, served_probe=probe,
        probe_latency=jnp.where(probe, completion - arrive, 0),
        probe_completion=jnp.where(probe, completion, 0),
        deferred=deferred,
        slot_ready_at=t_slot, ref_ready_at=ref_at,
    )
    cs = cs._replace(dev=dev, queue=q._replace(valid=valid),
                     hit_streak=streak, bh_sketch=sk, prac_count=prac)
    return cs, ev


# --------------------------------------------------------------------------
# Event horizon (the engine's fast-forward path)
# --------------------------------------------------------------------------

#: see ``repro.core.frontend.HORIZON_MAX`` — shared sentinel value
HORIZON_MAX = jnp.int32(1 << 30)


def channel_horizon(cspec: CompiledSpec, dp: D.DynParams,
                    cfg: ControllerConfig, cs: CtrlState, clk,
                    link_latency: int = 0):
    """Earliest cycle ``>= clk`` at which THIS channel could issue any
    command — queue candidate or refresh engine — evaluated on the
    current (post-cycle) state: the selection pass's lookup, made anew
    at ``clk``, reduced by :func:`idle_horizon`.

    CONSERVATIVE by construction: predicate, bus-kind, and scheduler
    masks are ignored (they only *shrink* the issue set, so ignoring
    them can only move the horizon earlier), and an early horizon merely
    executes an idle cycle.  What it must never do is overshoot, and it
    can't: every issue requires ``pre_pred`` (valid & timing-ready [&
    link-visible]) or a due+ready refresh unit, and both bounds are
    exact lower bounds on those events.  Between ``clk`` and the
    horizon the channel state is frozen (every controller/device update
    is gated on an issue), so the bound needs no re-evaluation until the
    next executed cycle.

    The engine computes the horizon only after a cycle that accepted
    and issued nothing.  The state it reads is then the one the
    controller's pass has just read, so the engine hands that pass's
    lookup (``StepEvents.slot_ready_at`` / ``ref_ready_at``) to
    :func:`idle_horizon` instead of calling this function
    (:func:`lookup_outlives_idle_cycle`).  Standards with WCK/RCK data
    clock sync are the exception: their prerequisite decode reads the
    clock, and a rank's data clock that stops at ``clk`` flips its
    column candidate between the pass's cycle and this one, so they
    recompute here.  This function stays the reference the tests hold
    the engine's reuse to.
    """
    q = cs.queue
    bank_hot = D.onehot(D.flat_bank(cspec, q.sub), cspec.n_banks)
    cand_cmd, _, _ = D.prereq(cspec, dp, cs.dev, q.is_write, q.sub, q.row,
                              clk)
    table = D.earliest_ready_table(cspec, dp, cs.dev)
    t_slot = D.table_at(table, cand_cmd, bank_hot, D.prereq_cmds(cspec))
    ref_at = _refresh_ready_at(cspec, table, _refresh_cmd(cspec, cs.dev))
    return idle_horizon(cspec, dp, cfg, cs, clk, t_slot, ref_at,
                        link_latency)


def idle_horizon(cspec: CompiledSpec, dp: D.DynParams,
                 cfg: ControllerConfig, cs: CtrlState, clk, t_slot, ref_at,
                 link_latency: int = 0):
    """:func:`channel_horizon` from a lookup already made: ``t_slot``,
    each queue slot's earliest issue clock of its prerequisite command
    (``table[cand_cmd, bank]``, the lookup the selection pipeline
    performs), and ``ref_at``, each refresh unit's of its PREab/REFab
    (:func:`_refresh_ready_at`).  Components:

    * queue: the minimum of ``t_slot`` over valid slots, floored at
      ``arrive + link_latency`` for a CXL link; candidates cannot flip
      while idle except via WCK/RCK clock expiry — bounded separately
      below;
    * refresh: per unit, ``max(due clock, ref_at)``; a PRAC alert makes
      the unit due NOW;
    * clock expiry (``data_clock_sync`` standards): the first
      ``clock_until`` still in the future, where a column candidate
      flips between RD/WR and its CAS/RCKSTRT sync prerequisite;
    * BlockHammer sketch decay: the next ``nREFI`` multiple (the sketch
      halves on those cycles, so they must be executed, not skipped).
    """
    q = cs.queue
    if link_latency:
        t_slot = jnp.maximum(t_slot, q.arrive + jnp.int32(link_latency))
    h = jnp.min(jnp.where(q.valid, t_slot, HORIZON_MAX),
                initial=HORIZON_MAX)
    if cfg.refresh_enabled:
        banks_per_ru = cspec.n_banks // cspec.n_refresh_units
        due_t = cs.dev.last_ref + dp.nREFI
        if cfg.prac_threshold:
            alert = jnp.max(
                (cs.prac_count >= cfg.prac_threshold).reshape(
                    cspec.n_refresh_units, banks_per_ru), axis=1)
            due_t = jnp.where(alert, clk, due_t)
        h = jnp.minimum(h, jnp.min(jnp.maximum(due_t, ref_at)))
    if cspec.data_clock_sync:
        with jax.named_scope("clock_sync"):
            cu = cs.dev.clock_until
            h = jnp.minimum(h, jnp.min(jnp.where(cu > clk, cu,
                                                 HORIZON_MAX)))
    if cfg.blockhammer_threshold:
        h = jnp.minimum(h, ((clk + dp.nREFI - jnp.int32(1)) // dp.nREFI)
                        * dp.nREFI)
    return jnp.maximum(h, clk)


def lookup_outlives_idle_cycle(cspec: CompiledSpec) -> bool:
    """Whether a selection pass's lookup at cycle ``t`` is still exact
    on the post-cycle state at ``t + 1`` when the cycle issued nothing:
    the earliest-issue table depends on the device state alone, and the
    prerequisite decode reads the clock only for data-clock-sync
    standards (a rank's WCK/RCK may stop in between)."""
    return not cspec.data_clock_sync


_IDLE_SLOT = dict(cmd=jnp.int32(-1), bank=jnp.int32(-1), row=jnp.int32(-1),
                  arrive=jnp.int32(-1), hit_ready=False)


def _pack_events(ev_col: dict, ev_row: dict | None = None) -> StepEvents:
    """Pack one or two selection-pass event dicts into ``StepEvents``.

    Per-bus-slot fields stack [col-bus, row-bus] (the row slot is idle for
    single-bus standards); per-cycle outcome fields OR/sum across passes —
    at most one pass can serve a given request, so the sums are exact.
    The lookups are the last pass's (on a cycle that issued nothing both
    passes saw the same state).
    """
    if ev_row is None:
        ev_row = dict(_IDLE_SLOT,
                      **{k: jnp.zeros_like(ev_col[k])
                         for k in ("served_read", "served_write",
                                   "served_probe", "probe_latency",
                                   "probe_completion", "deferred")},
                      slot_ready_at=ev_col["slot_ready_at"],
                      ref_ready_at=ev_col["ref_ready_at"])
    slot = {k: jnp.stack([jnp.asarray(ev_col[k]), jnp.asarray(ev_row[k])])
            for k in ("cmd", "bank", "row", "arrive", "hit_ready")}
    return StepEvents(
        **slot,
        served_read=ev_col["served_read"] | ev_row["served_read"],
        served_write=ev_col["served_write"] | ev_row["served_write"],
        served_probe=ev_col["served_probe"] | ev_row["served_probe"],
        probe_latency=ev_col["probe_latency"] + ev_row["probe_latency"],
        probe_completion=(ev_col["probe_completion"]
                          + ev_row["probe_completion"]),
        deferred=ev_col["deferred"] + ev_row["deferred"],
        slot_ready_at=ev_row["slot_ready_at"],
        ref_ready_at=ev_row["ref_ready_at"],
    )


def controller_step(cspec: CompiledSpec, dp: D.DynParams, cfg: ControllerConfig,
                    cs: CtrlState, clk, link_latency: int = 0) -> tuple:
    """One controller cycle for ONE channel.  Dual-C/A standards run the
    selection pipeline twice — a column pass and a row pass (paper §2);
    others run it once.  The engine vmaps this function across each spec
    group's channels inside its cycle scan; CXL-attached groups pass their
    static ``link_latency``, applied at the enqueue boundary (request
    visibility) and the completion boundary (read-data return)."""
    preds = cfg.predicates()
    sched_fn = SCHEDULERS[cfg.scheduler]
    n_kinds = 4

    if cspec.dual_command_bus:
        col_ok = np.asarray(
            [k in (S.KIND_COL, S.KIND_SYNC) for k in range(n_kinds)])
        row_ok = np.asarray(
            [k in (S.KIND_ROW, S.KIND_REF) for k in range(n_kinds)])
        cs, ev_col = _select_and_issue(cspec, dp, cs, clk, cfg, preds,
                                       col_ok, sched_fn, link_latency)
        cs, ev_row = _select_and_issue(cspec, dp, cs, clk, cfg, preds,
                                       row_ok, sched_fn, link_latency)
        events = _pack_events(ev_col, ev_row)
    else:
        all_ok = np.ones((n_kinds,), bool)
        cs, ev = _select_and_issue(cspec, dp, cs, clk, cfg, preds, all_ok,
                                   sched_fn, link_latency)
        events = _pack_events(ev)
    return cs, events
