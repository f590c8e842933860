"""Plain reference for an LPDDR5 memory system: a straightforward
cycle-by-cycle simulator in numpy, written from the JEDEC JESD209-5
LPDDR5 rules, the configuration file and its stated policies alone.  It
imports nothing of the program; from ``bench/reference.py`` it takes the
generator, the address layout, the latency resolver and its constants.

What it simulates, cycle by cycle, for ``n_cycles`` from empty queues,
closed banks and stopped data clocks:

1. The frontend, as ``bench/reference.py`` describes it (copied below:
   one random read probe in flight, an open-loop stream at ``interval``
   decoded through the mapper, one request per cycle at most).
2. Each channel's controller, FR-FCFS over one queue, one command per
   cycle on the channel's single command bus (JESD209-5 has one CA bus
   per channel).  A request's next command is
   - ``ACT1`` when its bank is closed: ACT-1 starts an activation and
     latches the row (JESD209-5 splits ACTIVATE into ACT-1 and ACT-2);
     the bank is Activating until its ACT-2;
   - ``ACT2`` when its bank is Activating: ACT-2 completes the pending
     activation, for the row ACT-1 latched (the configuration's policy:
     any request to an Activating bank may send it, for that row);
   - ``CAS_RD`` / ``CAS_WR`` when its row is open and its rank's data
     clock is stopped: the CAS command with the WCK2CK-sync bits that
     JESD209-5 requires before data moves on a stopped WCK (the
     configuration's policy issues it as soon as the row is open, not
     directly before its RD/WR: ``assumed.departures``);
   - ``RD`` / ``WR`` when its row is open and its rank's clock runs;
   - ``PRE`` when another row is open.
   A command may issue once every timing constraint below is met, and
   under the configuration's two ACT-2 rules: JESD209-5's tAAD bounds
   ACT-1 to ACT-2 by ``nAAD``; the controller makes an ACT-2 urgent once
   ``clk + 2 >= ACT-1 clock + nAAD``, and while any bank of the channel
   has an urgent ACT-2 only urgent ACT-2 candidates may issue (the others
   count as deferred).
3. The data clock of each rank (JESD209-5's WCK, started by a CAS with
   WCK2CK sync): a CAS starts it until ``clk + nWCKIDLE``; each RD/WR on
   the rank keeps it on until at least ``clk + nWCKIDLE`` (the
   configuration's stand-in for the standard's WCK-always-on window and
   its postamble).
4. Refresh, as ``bench/reference.py`` does it per rank: all-bank refresh
   due every ``nREFI`` (channels staggered by ``c * nREFI / C``), PREab
   while any bank of the rank is open or Activating, then REFab; it
   yields to pending requests of its rank until ``refresh_urgent_margin``
   cycles overdue, after which those requests are held (deferred).  An
   urgent PREab closes an Activating bank too, whose request then starts
   again with ACT-1 (the configuration's policy; JESD209-5 has no
   precharge between ACT-1 and ACT-2).
5. Statistics, as ``bench/reference.py`` keeps them.

Timing constraints are a table ``earliest[cmd, bank]`` that each issue
raises for the banks under the constrained node (level 0 channel, 1
rank, 2 bank group, 3 bank; the four-activate window as the times of
each rank's last four ACT-1s).  JESD209-5 names, and the repo's edges:

- tRCD (ACT-2 to RD/WR) and tRAS (ACT-2 to PRE, and to PREab) from
  ACT-2, which opens the row;
- tRP (PRE or PREab to ACT-1), tRC (ACT-2 to the bank's next ACT-1),
  tRRD and tFAW (ACT-1 to ACT-1 per rank and bank group) to or between
  ACT-1s, which start activations;
- tAAD's minimum ``nAAD_MIN``: ACT-1 to ACT-2 of the bank;
- tCCD, the read/write turnarounds tWTR and the data bus's burst time
  ``nBL``, tRTP and the write recovery to PRE/PREab, tRFCab, as in the
  DDR skeleton of ``bench/reference.py``;
- the WCK2CK sync: ``nWCKEN`` from a CAS to an RD or WR of its rank, and
  between two CASes of one rank (the configuration's single lead time
  for JESD209-5's tWCKENL + tWCKPRE).

``control=True`` breaks the data-clock guarantee that the configuration
states, so that the comparison can be shown to fail: ``nWCKEN`` one
cycle short.
"""
from __future__ import annotations

import numpy as np

from reference import BIG, NEG, layout, lcg, resolve

#: command set in the program's order: name -> hierarchy level it
#: addresses (1 the rank, 3 the bank)
COMMANDS = {"ACT1": 3, "ACT2": 3, "PRE": 3, "PREab": 1, "RD": 3, "WR": 3,
            "REFab": 1, "CAS_RD": 1, "CAS_WR": 1}
NAMES = list(COMMANDS)
CLOSED, ACTIVATING = -1, -2
SYNC = ["CAS_RD", "CAS_WR"]

#: JESD209-5 timing skeleton (level, preceding, following, latency,
#: window): level 0 channel, 1 rank, 2 bank group, 3 bank
SKELETON = [
    (3, ["ACT2"], ["RD", "WR"], "nRCD", 1),
    (3, ["ACT2"], ["PRE"], "nRAS", 1),
    (3, ["PRE"], ["ACT1"], "nRP", 1),
    (3, ["ACT2"], ["ACT1"], "nRC", 1),
    (3, ["RD"], ["PRE"], "nRTP", 1),
    (3, ["WR"], ["PRE"], "nCWL+nBL+nWR", 1),
    (3, ["ACT1"], ["ACT2"], "nAAD_MIN", 1),
    (1, ["ACT1"], ["ACT1"], "nRRD_S", 1),
    (1, ["ACT1"], ["ACT1"], "nFAW", 4),
    (1, ["RD"], ["RD"], "nCCD_S", 1),
    (1, ["WR"], ["WR"], "nCCD_S", 1),
    (1, ["RD"], ["WR"], "nCL+nBL+2-nCWL", 1),
    (1, ["WR"], ["RD"], "nCWL+nBL+nWTR_S", 1),
    (1, ["RD"], ["PREab"], "nRTP", 1),
    (1, ["WR"], ["PREab"], "nCWL+nBL+nWR", 1),
    (1, ["ACT2"], ["PREab"], "nRAS", 1),
    (1, ["PREab", "PRE"], ["REFab"], "nRP", 1),
    (1, ["REFab"], ["REFab"], "nRFC", 1),
    (1, ["REFab"], ["ACT1", "RD", "WR"], "nRFC", 1),
    (1, ["PREab"], ["ACT1"], "nRP", 1),
    (1, SYNC, ["RD", "WR"], "nWCKEN", 1),
    (1, SYNC, SYNC, "nWCKEN", 1),
    (0, ["RD"], ["RD"], "nBL", 1),
    (0, ["WR"], ["WR"], "nBL", 1),
    (0, ["RD"], ["WR"], "nBL", 1),
    (0, ["WR"], ["RD"], "nBL", 1),
    (2, ["RD"], ["RD"], "nCCD_L", 1),
    (2, ["WR"], ["WR"], "nCCD_L", 1),
    (2, ["ACT1"], ["ACT1"], "nRRD_L", 1),
    (2, ["WR"], ["RD"], "nCWL+nBL+nWTR_L", 1),
]


class Memory:
    """Controllers and devices of every channel; arrays carry a leading
    channel axis."""

    def __init__(self, config: dict, timings: dict):
        if config["standard"] != "LPDDR5":
            raise ValueError(f"bench/reference_lpddr5.py models LPDDR5, "
                             f"not {config['standard']}")
        org = config["organization"]
        self.levels = list(org["levels"])
        counts = [1] + [int(org["levels"][n]) for n in self.levels]
        self.C = C = int(config["channels"])
        Q = int(config["controller"]["queue_depth"])
        self.R = counts[1]
        self.NB = NB = int(np.prod(counts))
        self.bpr = NB // self.R
        self.span = [int(np.prod(counts[lv + 1:])) for lv in range(4)]
        self.nBL = int(timings["nBL"])
        self.read_latency = int(timings["nCL"]) + self.nBL
        self.nREFI = int(timings["nREFI"])
        self.nAAD = int(timings["nAAD"])
        self.wck_idle = int(timings["nWCKIDLE"])
        self.margin = int(config["controller"]["refresh_urgent_margin"])
        self.ci = np.arange(C)[:, None]
        self.id = {n: i for i, n in enumerate(NAMES)}
        self._plan(timings)

        self.earliest = np.full((C, len(NAMES), NB), NEG, np.int64)
        self.rings = {k: np.full((C, NB // self.span[k[1]], w), NEG,
                                 np.int64) for k, w in self.ring_depth.items()}
        self.row = np.full((C, NB), CLOSED, np.int64)
        self.act1_row = np.zeros((C, NB), np.int64)
        self.act1_clk = np.full((C, NB), NEG, np.int64)
        self.clock_until = np.zeros((C, self.R), np.int64)
        stagger = bool(config["controller"]["refresh_stagger"]) and C > 1
        self.last_ref = np.repeat(
            np.array([-(c * self.nREFI // C) if stagger else 0
                      for c in range(C)], np.int64)[:, None], self.R, 1)
        z = lambda *sh: np.zeros(sh, np.int64)
        self.q_valid = np.zeros((C, Q), bool)
        self.q_probe = np.zeros((C, Q), bool)
        self.q_final = z(C, Q)      # RD or WR: the request's column command
        self.q_sync = z(C, Q)       # CAS_RD or CAS_WR: its clock start
        self.q_bank, self.q_ru = z(C, Q), z(C, Q)
        self.q_row, self.q_arrive = z(C, Q), z(C, Q)
        self.reads, self.writes, self.plat = z(C), z(C), z(C)
        self.pcnt, self.busy, self.deferred = z(C), z(C), z(C)
        self.cmds = z(C, len(NAMES))
        #: every command issued: (clk, channel, name, bank, rank, row)
        self.log = []

    def _plan(self, t: dict):
        """Per (preceding command, level): the rows of ``earliest`` its
        issue raises (largest latency per following command), and its
        windowed constraints."""
        raise_by, self.windows, self.ring_depth = {}, {}, {}
        for level, prev, follow, lat, win in SKELETON:
            cycles = resolve(lat, t)
            for p in prev:
                if level > COMMANDS[p]:
                    continue    # p never addresses a node at this level
                for f in follow:
                    if win == 1:
                        d = raise_by.setdefault((p, level), {})
                        d[f] = max(d.get(f, NEG), cycles)
                    else:
                        self.windows.setdefault((p, level), []).append(
                            (win, self.id[f], cycles))
                        self.ring_depth[(p, level)] = max(
                            self.ring_depth.get((p, level), 0), win)
        self.raises = {}
        for (p, level), d in raise_by.items():
            self.raises.setdefault(p, []).append(
                (level, np.array([self.id[f] for f in d]),
                 np.array(list(d.values()), np.int64)[:, None]))

    # -- queues -------------------------------------------------------------
    def insert(self, c, bank, ru, row, write, probe, clk) -> bool:
        free = np.flatnonzero(~self.q_valid[c])
        if not len(free):
            return False
        s = free[0]
        self.q_valid[c, s] = True
        self.q_final[c, s] = self.id["WR" if write else "RD"]
        self.q_sync[c, s] = self.id["CAS_WR" if write else "CAS_RD"]
        self.q_probe[c, s] = probe
        self.q_bank[c, s], self.q_ru[c, s] = bank, ru
        self.q_row[c, s], self.q_arrive[c, s] = row, clk
        return True

    # -- device --------------------------------------------------------------
    def issue(self, c, name, bank, ru, row, clk):
        self.cmds[c, self.id[name]] += 1
        self.log.append((clk, c, name, bank, ru, row))
        for level, fs, lats in self.raises.get(name, ()):
            lo = bank // self.span[level] * self.span[level]
            hi = lo + self.span[level]
            self.earliest[c, fs, lo:hi] = np.maximum(
                self.earliest[c, fs, lo:hi], clk + lats)
        for level in range(COMMANDS[name] + 1):
            key = (name, level)
            if key not in self.rings:
                continue
            node = bank // self.span[level]
            ring = self.rings[key][c, node]
            ring[1:] = ring[:-1].copy()
            ring[0] = clk
            lo = node * self.span[level]
            hi = lo + self.span[level]
            for win, f, lat in self.windows[key]:
                if ring[win - 1] > NEG:
                    self.earliest[c, f, lo:hi] = np.maximum(
                        self.earliest[c, f, lo:hi], ring[win - 1] + lat)
        if name == "ACT1":
            self.row[c, bank] = ACTIVATING
            self.act1_row[c, bank], self.act1_clk[c, bank] = row, clk
        elif name == "ACT2":
            self.row[c, bank] = self.act1_row[c, bank]
        elif name == "PRE":
            self.row[c, bank] = CLOSED
        elif name in ("PREab", "REFab"):
            self.row[c, ru * self.bpr:(ru + 1) * self.bpr] = CLOSED
            if name == "REFab":
                self.last_ref[c, ru] = clk
        elif name in SYNC:
            self.clock_until[c, ru] = clk + self.wck_idle
        else:                                   # RD, WR
            self.clock_until[c, ru] = max(self.clock_until[c, ru],
                                          clk + self.wck_idle)

    # -- one cycle of every channel -------------------------------------------
    def step(self, clk) -> int | None:
        """Issue at most one command per channel; returns the completion
        clock of a probe served, if any."""
        age = clk - self.last_ref
        due = age >= self.nREFI
        if not self.q_valid.any() and not due.any():
            return None         # nothing can issue on this cycle
        rs = self.row[self.ci, self.q_bank]
        hit = rs == self.q_row
        clock_on = clk < self.clock_until[self.ci, self.q_ru]
        cand = np.where(
            rs == CLOSED, self.id["ACT1"],
            np.where(rs == ACTIVATING, self.id["ACT2"],
                     np.where(hit, np.where(clock_on, self.q_final,
                                            self.q_sync), self.id["PRE"])))
        ready = clk >= self.earliest[self.ci, cand, self.q_bank]
        allowed = self.q_valid & ready
        # refresh urgency holds the requests of an overdue rank
        urgent = due & (age >= self.nREFI + self.margin)
        held = urgent[self.ci, self.q_ru]
        # tAAD: an urgent ACT-2 anywhere on the channel admits only
        # urgent ACT-2s
        act2_urgent = (self.row == ACTIVATING) & (
            clk + 2 >= self.act1_clk + self.nAAD)
        mine = (cand == self.id["ACT2"]) & act2_urgent[self.ci, self.q_bank]
        held |= act2_urgent.any(1)[:, None] & ~mine
        self.deferred += np.sum(allowed & held, 1)
        allowed &= ~held
        refresh = np.zeros(self.C, bool)
        if due.any():
            # the refresh engine: the most overdue due rank first
            ru = np.argmax(np.where(due, age, -1), 1)
            C = np.arange(self.C)
            open_ = (self.row.reshape(self.C, self.R, self.bpr)
                     != CLOSED).any(2)[C, ru]
            rcmd = np.where(open_, self.id["PREab"], self.id["REFab"])
            rep = ru * self.bpr
            pending = (self.q_valid & (self.q_ru == ru[:, None])).any(1)
            refresh = (due.any(1) & (clk >= self.earliest[C, rcmd, rep])
                       & (urgent[C, ru] | ~pending))
            allowed &= ~refresh[:, None]
            for c in np.flatnonzero(refresh):
                self.issue(c, NAMES[rcmd[c]], int(rep[c]), int(ru[c]), 0,
                           clk)
        # FR-FCFS: the oldest ready row hit (CAS or RD/WR), else the oldest
        # ready request
        hits = allowed & hit
        pick = np.where(hits.any(1)[:, None], hits, allowed)
        slot = np.argmin(np.where(pick, self.q_arrive, BIG), 1)
        done = None
        for c in np.flatnonzero(pick.any(1)):
            s = slot[c]
            name = NAMES[cand[c, s]]
            self.issue(c, name, int(self.q_bank[c, s]), int(self.q_ru[c, s]),
                       int(self.q_row[c, s]), clk)
            if name in ("RD", "WR"):
                self.q_valid[c, s] = False
                self.busy[c] += self.nBL
                if name == "WR":
                    self.writes[c] += 1
                    continue
                self.reads[c] += 1
                if self.q_probe[c, s]:
                    end = clk + self.read_latency
                    self.pcnt[c] += 1
                    self.plat[c] += end - self.q_arrive[c, s]
                    done = end
        return done


def run(config: dict, interval: float, read_ratio: float, seed: int,
        n_cycles: int, control: bool = False) -> Memory:
    """The memory system after ``n_cycles`` of one run (its ``log`` holds
    every command issued)."""
    timings = dict(config["timings"])
    if control:
        timings["nWCKEN"] -= 1
    mem = Memory(config, timings)
    lay = layout(config)
    sub = mem.levels
    counts = {n: int(config["organization"]["levels"][n]) for n in sub}
    front = config["frontend"]
    gap = int(front["probe_gap"])
    cap = 256 * int(front["max_backlog"])
    interval_fp = max(int(interval * 256), 1)
    read_fp = int(read_ratio * 256)

    def place(fields):
        bank = 0
        for n in sub:
            bank = bank * counts[n] + int(fields[n])
        return int(fields["channel"]), bank, int(fields[sub[0]]), \
            int(fields["row"])

    x = (int(seed) & 0xFFFFFFFF) | 1
    accum = seq = 0
    probe_busy, probe_next = False, 0
    for clk in range(n_cycles):
        fields = {}
        for name, count in lay:
            x = lcg(x)
            fields[name] = (x >> 8) % count
        okp = False
        if not probe_busy and clk >= probe_next:
            c, bank, ru, row = place(fields)
            okp = mem.insert(c, bank, ru, row, False, True, clk)
        accum = min(accum + 256, cap)
        rest, fields = seq, {}
        for name, count in lay:
            fields[name] = rest % count
            rest //= count
        x = lcg(x)
        write = ((x >> 9) % 256) >= read_fp
        ok = False
        if accum >= interval_fp:
            c, bank, ru, row = place(fields)
            ok = mem.insert(c, bank, ru, row, write, False, clk)
        done = mem.step(clk)
        probe_busy = probe_busy or okp
        if ok:
            accum -= interval_fp
            seq += 1
        if done is not None:
            probe_busy, probe_next = False, done + gap
    return mem


def simulate(config: dict, interval: float, read_ratio: float, seed: int,
             n_cycles: int, control: bool = False) -> dict:
    """The statistics of one run, as ``{leaf: int64 array}``: the summed
    counters, ``per_channel.*`` and ``per_group.0.*`` (one group)."""
    mem = run(config, interval, read_ratio, seed, n_cycles, control)
    ch = {"reads_done": mem.reads, "writes_done": mem.writes,
          "probe_lat_sum": mem.plat, "probe_cnt": mem.pcnt,
          "data_bus_busy": mem.busy, "cmd_counts": mem.cmds,
          "deferred": mem.deferred}
    out = {"cycles": np.asarray(n_cycles, np.int64)}
    for k, v in ch.items():
        out[k] = v.sum(0)
        out[f"per_channel.{k}"] = v.copy()
        out[f"per_group.0.{k}"] = v.copy()
    return out
