"""Plain reference: a straightforward cycle-by-cycle memory-system
simulator in numpy, written from the published DDR5 / HBM3 rules and the
configuration file alone.  It imports nothing of the program.

What it simulates, cycle by cycle, for ``n_cycles`` from empty queues and
closed rows:

1. The frontend.  A 32-bit linear congruential generator (``x * 1664525
   + 1013904223``) seeded with ``seed | 1``.  While no probe is in flight
   and its gap has passed, one random read probe is drawn (one draw per
   address field, ``(x >> 8) % count``; drawn every cycle, inserted only
   when wanted).  A stream request is due whenever the arrival
   accumulator (``+256`` a cycle, capped at ``256 * max_backlog``) holds
   ``interval * 256``; its address is the linear request counter decoded
   through the mapper order, and it is a write when ``(x >> 9) % 256 >=
   read_ratio * 256`` after one more draw.  Each request enters the first
   free slot of its channel's queue, or waits when that queue is full.
2. Each channel's controller: FR-FCFS over its queue.  A request's next
   command is ACT (bank closed), RD/WR (its row open) or PRE (another row
   open); it may issue once every timing constraint is met.  All-bank
   refresh is due every ``nREFI`` per refresh unit (channels staggered by
   ``c * nREFI / C``), issued as PREab then REFab, yielding to pending
   requests of its unit until it is ``refresh_urgent_margin`` cycles
   overdue, after which those requests are held (and counted as
   deferred).  A standard with a dual command bus issues up to one column
   and one row command per cycle (column first); the others one command.
3. Statistics: requests served, probe latency (RD issue + nCL + nBL -
   arrival), data-bus cycles (nBL per request), commands issued and
   deferred candidates, per channel and summed.

Timing constraints are kept as a table ``earliest[cmd, bank]`` that each
issue raises for the banks under the constrained node: the JEDEC
skeleton below, with latencies resolved from the configuration's
published numbers, and the four-activate window kept as the times of
each rank's last four ACTs.

``control=True`` breaks one JEDEC guarantee that the configuration
states, so that the comparison can be shown to fail: RD/WR may follow ACT
one cycle before tRCD has passed.
"""
from __future__ import annotations

import re

import numpy as np

NEG = -(1 << 28)
BIG = 1 << 40
ROW, COL, REF = 0, 1, 2

#: command set: name -> (hierarchy level it addresses, bus kind);
#: level 1 is the refresh unit (rank / pseudo-channel), 3 the bank
COMMANDS = {"ACT": (3, ROW), "PRE": (3, ROW), "PREab": (1, ROW),
            "RD": (3, COL), "WR": (3, COL), "REFab": (1, REF)}
NAMES = list(COMMANDS)
#: standard -> whether row and column commands issue on separate buses
DUAL_BUS = {"DDR5": False, "HBM3": True}

#: JEDEC timing skeleton (level, preceding, following, latency, window):
#: level 0 channel (shared data bus), 1 refresh unit, 2 bank group, 3 bank
SKELETON = [
    (3, ["ACT"], ["RD", "WR"], "nRCD", 1),
    (3, ["ACT"], ["PRE"], "nRAS", 1),
    (3, ["PRE"], ["ACT"], "nRP", 1),
    (3, ["ACT"], ["ACT"], "nRC", 1),
    (3, ["RD"], ["PRE"], "nRTP", 1),
    (3, ["WR"], ["PRE"], "nCWL+nBL+nWR", 1),
    (1, ["ACT"], ["ACT"], "nRRD_S", 1),
    (1, ["ACT"], ["ACT"], "nFAW", 4),
    (1, ["RD"], ["RD"], "nCCD_S", 1),
    (1, ["WR"], ["WR"], "nCCD_S", 1),
    (1, ["RD"], ["WR"], "nCL+nBL+2-nCWL", 1),
    (1, ["WR"], ["RD"], "nCWL+nBL+nWTR_S", 1),
    (1, ["RD"], ["PREab"], "nRTP", 1),
    (1, ["WR"], ["PREab"], "nCWL+nBL+nWR", 1),
    (1, ["ACT"], ["PREab"], "nRAS", 1),
    (1, ["PREab", "PRE"], ["REFab"], "nRP", 1),
    (1, ["REFab"], ["REFab"], "nRFC", 1),
    (1, ["REFab"], ["ACT", "RD", "WR"], "nRFC", 1),
    (1, ["PREab"], ["ACT"], "nRP", 1),
    (0, ["RD"], ["RD"], "nBL", 1),
    (0, ["WR"], ["WR"], "nBL", 1),
    (0, ["RD"], ["WR"], "nBL", 1),
    (0, ["WR"], ["RD"], "nBL", 1),
    (2, ["RD"], ["RD"], "nCCD_L", 1),
    (2, ["WR"], ["WR"], "nCCD_L", 1),
    (2, ["ACT"], ["ACT"], "nRRD_L", 1),
    (2, ["WR"], ["RD"], "nCWL+nBL+nWTR_L", 1),
]

def resolve(expr: str, t: dict) -> int:
    total = 0
    for sign, tok in re.findall(r"([+-]?)\s*([A-Za-z_][A-Za-z_0-9]*|\d+)",
                                expr):
        v = int(tok) if tok.isdigit() else int(t[tok])
        total += -v if sign == "-" else v
    return total


def lcg(x: int) -> int:
    return (x * 1664525 + 1013904223) & 0xFFFFFFFF


def layout(config: dict) -> list:
    """Address fields, least significant first, of the mapper order
    (``Ro``/``Co``/``Ba``/``Ra``/``Ch`` from most to least significant)."""
    org = config["organization"]
    levels = list(org["levels"].items())
    banks = [(n, int(c)) for n, c in levels if n in ("bankgroup", "bank")]
    ranks = [(n, int(c)) for n, c in levels if n not in ("bankgroup", "bank")]
    fields = {"Ch": [("channel", int(config["channels"]))], "Ra": ranks,
              "Ba": banks, "Ro": [("row", int(org["rows"]))],
              "Co": [("col", int(org["columns"]))]}
    order = config["frontend"]["mapper"]
    toks = [order[i:i + 2] for i in range(0, len(order), 2)]
    return [f for tok in reversed(toks) for f in fields[tok]]


class Memory:
    """Controllers and devices of every channel; arrays carry a leading
    channel axis."""

    def __init__(self, config: dict, timings: dict):
        org = config["organization"]
        self.levels = [n for n in org["levels"]]
        counts = [1] + [int(org["levels"][n]) for n in self.levels]
        self.C = C = int(config["channels"])
        self.Q = Q = int(config["controller"]["queue_depth"])
        self.R = counts[1]
        self.NB = NB = int(np.prod(counts))
        self.bpr = NB // self.R
        # banks under one node of each level
        self.span = [int(np.prod(counts[lv + 1:])) for lv in range(4)]
        self.t = timings
        self.nBL = int(timings["nBL"])
        self.read_latency = int(timings["nCL"]) + self.nBL
        self.nREFI = int(timings["nREFI"])
        self.margin = int(config["controller"]["refresh_urgent_margin"])
        self.dual = DUAL_BUS[config["standard"]]
        self.kind = np.array([COMMANDS[n][1] for n in NAMES])
        self.col_bus = np.array([False, True, False])
        self.row_bus = np.array([True, False, True])
        self.any_bus = np.ones(3, bool)
        self.ci = np.arange(C)[:, None]
        self.id = {n: i for i, n in enumerate(NAMES)}
        self._plan(timings)

        self.earliest = np.full((C, len(NAMES), NB), NEG, np.int64)
        self.rings = {k: np.full((C, NB // self.span[k[1]], w), NEG,
                                 np.int64) for k, w in self.ring_depth.items()}
        self.row = np.full((C, NB), -1, np.int64)
        stagger = bool(config["controller"]["refresh_stagger"]) and C > 1
        self.last_ref = np.repeat(
            np.array([-(c * self.nREFI // C) if stagger else 0
                      for c in range(C)], np.int64)[:, None], self.R, 1)
        z = lambda *sh: np.zeros(sh, np.int64)
        self.q_valid = np.zeros((C, Q), bool)
        self.q_probe = np.zeros((C, Q), bool)
        self.q_final = z(C, Q)      # RD or WR: the request's column command
        self.q_bank, self.q_ru = z(C, Q), z(C, Q)
        self.q_row, self.q_arrive = z(C, Q), z(C, Q)
        self.reads, self.writes, self.plat = z(C), z(C), z(C)
        self.pcnt, self.busy, self.deferred = z(C), z(C), z(C)
        self.cmds = z(C, len(NAMES))

    def _plan(self, t: dict):
        """Per preceding command: the rows of ``earliest`` that its issue
        raises, by level (largest latency per following command)."""
        raise_by = {}
        self.windows = {}
        self.ring_depth = {}
        for level, prev, follow, lat, win in SKELETON:
            cycles = resolve(lat, t)
            for p in prev:
                if level > COMMANDS[p][0]:
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
        self.q_probe[c, s] = probe
        self.q_bank[c, s], self.q_ru[c, s] = bank, ru
        self.q_row[c, s], self.q_arrive[c, s] = row, clk
        return True

    # -- device --------------------------------------------------------------
    def issue(self, c, name, bank, ru, row, clk):
        self.cmds[c, self.id[name]] += 1
        for level, fs, lats in self.raises.get(name, ()):
            lo = bank // self.span[level] * self.span[level]
            hi = lo + self.span[level]
            self.earliest[c, fs, lo:hi] = np.maximum(
                self.earliest[c, fs, lo:hi], clk + lats)
        for level in range(COMMANDS[name][0] + 1):
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
        if name == "ACT":
            self.row[c, bank] = row
        elif name == "PRE":
            self.row[c, bank] = -1
        elif name in ("PREab", "REFab"):
            self.row[c, ru * self.bpr:(ru + 1) * self.bpr] = -1
            if name == "REFab":
                self.last_ref[c, ru] = clk

    # -- one selection pass over every channel --------------------------------
    def select(self, clk, kind_ok) -> int | None:
        """Issue at most one command per channel whose bus kind
        ``kind_ok[kind]`` allows; returns the completion clock of a probe
        served, if any."""
        rs = self.row[self.ci, self.q_bank]
        hit = rs == self.q_row
        cand = np.where(rs == -1, self.id["ACT"],
                        np.where(hit, self.q_final, self.id["PRE"]))
        ready = clk >= self.earliest[self.ci, cand, self.q_bank]
        allowed = self.q_valid & ready & kind_ok[self.kind[cand]]
        age = clk - self.last_ref
        due = age >= self.nREFI
        refresh = np.zeros(self.C, bool)
        if due.any():
            urgent = due & (age >= self.nREFI + self.margin)
            held = urgent[self.ci, self.q_ru]
            self.deferred += np.sum(allowed & held, 1)
            allowed &= ~held
            # the refresh engine: the most overdue due unit first
            ru = np.argmax(np.where(due, age, -1), 1)
            C = np.arange(self.C)
            open_ = (self.row.reshape(self.C, self.R, self.bpr)
                     != -1).any(2)[C, ru]
            rcmd = np.where(open_, self.id["PREab"], self.id["REFab"])
            rep = ru * self.bpr
            pending = (self.q_valid & (self.q_ru == ru[:, None])).any(1)
            refresh = (due.any(1) & (clk >= self.earliest[C, rcmd, rep])
                       & kind_ok[self.kind[rcmd]]
                       & (urgent[C, ru] | ~pending))
            allowed &= ~refresh[:, None]
            for c in np.flatnonzero(refresh):
                self.issue(c, NAMES[rcmd[c]], int(rep[c]), int(ru[c]), 0,
                           clk)
        # FR-FCFS: the oldest ready row hit, else the oldest ready request
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

    def step(self, clk) -> int | None:
        """One controller cycle of every channel."""
        if not self.q_valid.any() and not (
                clk - self.last_ref >= self.nREFI).any():
            return None         # nothing can issue on this cycle
        if self.dual:
            a = self.select(clk, self.col_bus)
            b = self.select(clk, self.row_bus)
            return a if a is not None else b
        return self.select(clk, self.any_bus)


def simulate(config: dict, interval: float, read_ratio: float, seed: int,
             n_cycles: int, control: bool = False) -> dict:
    """The statistics of one run, as ``{leaf: int64 array}``: the summed
    counters, ``per_channel.*`` and ``per_group.0.*`` (one group)."""
    timings = dict(config["timings"])
    if control:
        timings["nRCD"] -= 1
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
