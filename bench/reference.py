"""Plain reference scheduler: the semantics the benchmark holds the program to.

A straightforward, single-threaded re-implementation of what the fleet
scheduler decides, written from its specification and importing nothing of
the program.  Given a configuration (nodes, application profiles, EcoSched
weights), an arrival stream and the elastic settings, it replays the stream
and returns every schedule record and the total energy.

Semantics, in the order a replay applies them:

* Events pop by (time, kind, push order), kinds ARRIVAL < COMPLETE <
  PREEMPT < RESUME.  All arrivals of one instant are routed before any node
  decides; nodes then decide in the order they were first touched.
* Dispatch routes each arrival to the node minimising
  ``E* * (drain + t*) / t*``: (E*, t*) is the app's least-energy mode on that
  node's hardware and ``drain`` the node's committed busy unit-seconds per
  unit (running tails plus the cheapest work of every waiting job).  Ties
  go to the node whose name sorts first.
* A node decides over the first ``window`` waiting jobs.  Each job's modes
  come from brief profiling (runtime inverted from the DRAM-utilisation
  signal when the profile has one), normalised to the job's fastest and
  cheapest modes, and filtered to a slowdown of at most ``1 + tau``.  Every
  feasible joint action (at most the free isolation domains many jobs,
  units placeable by domain-spreading first fit) is scored by Eq. (1)
  ``mean(E_norm - 1) + lam * (G_free - G(a)) / M (+ lam_f * mean f)``; the
  least score wins, then the most units, then the first action in
  enumeration order (size, then job positions, then modes).  An idle node
  never chooses the empty action while another is feasible.
* With resizing on, a completion first offers each running job a
  checkpoint-and-relaunch at a better (count, frequency) mode: the
  alternative must win Eq. (1) on the node with the job's units freed by
  ``switch_cost`` and save more than ``min_gain_s`` of predicted remaining
  time; the largest saving is taken.  Checkpoints hold the units for
  ``ckpt_time`` at ``ckpt_power_scale`` times busy power; the relaunch pays
  ``restart_time`` and runs only the remaining work.
* Energy is exact and piecewise constant: busy power times each segment's
  duration, idle power per free unit integrated between events up to each
  node's last event, and the fleet's tail idle up to the makespan.

With a fault section (the traffic file's ``faults``, every field stated),
the fault plane of the program's ``core/faults.py`` and ``core/events.py``
docstrings, copied here from that specification:

* Streams.  Every draw comes from a named stream,
  ``random.Random(int.from_bytes(sha256(f"{seed}:{key}")[:8], "big"))``,
  and an exponential draw of mean ``m`` is ``-m * log(u)`` on the stream's
  next ``u`` (redrawn while ``u <= 1e-12``).  Keys: ``node:<name>`` (one
  stream a node, drawn cycle after cycle), ``job:<job>:<segment>`` and
  ``straggle:<job>:<segment>`` (one fresh stream a segment).
* Kinds, in tie-break order after RESUME: MIGRATE < NODE_FAIL <
  NODE_RECOVER < JOB_FAIL < RETRY.  NODE_FAIL and NODE_RECOVER are the
  node timeline; every other kind is work.
* Node cycles.  After the opening pass each node, in fleet order, draws
  its first cycle: up time (mean ``node_mtbf_s``), down time (mean
  ``node_mttr_s``), then a failure that is partial (``degrade_units``
  units, at most the node's) with probability ``degrade_frac``, else
  whole.  A failure at ``t`` advances the node's clock, kills every job
  on a victim unit (the highest alive units), marks the victims dead,
  backfills the survivors' queue if a unit is free, and schedules the
  repair at ``t + down``; a repair revives them, decides if anything
  waits, and draws the next cycle from ``t``.
* Dead units.  A dead unit is neither free nor placeable, so placement,
  the idle-energy integral and the free count see it; Eq. (1)'s ``M`` is
  the alive units; routing sees each node's tables rebuilt for its alive
  units (fit, cheapest busy unit-seconds, least-energy mode; the drain
  divisor ``max(alive, 1)``) at every failure and repair, and its waiting
  work re-summed under the new tables.  The waiting jobs of a fully dead
  node wait for the repair: the program reroutes them only through
  migration, which the reference refuses.
* Held arrivals.  An arrival that no node fits now, but some healthy node
  would, comes back ``retry_base_s`` later as a new arrival.
* Crashes and stragglers.  Segment ``s`` of a job (numbered per node from
  0 once faults or elastic settings are on) runs ``straggler_factor``
  times longer with probability ``straggler_prob``, and crashes at
  ``start + exp(job_mtbf_s)`` of its own stream when that comes before
  its end.  A crash of a running job that is not writing a checkpoint
  kills it and backfills the node if a unit is free.
* Kills.  A killed job's segment ends at the kill (kind ``fail``): the
  unrun part of its charged energy is refunded (at ``ckpt_power_scale``
  for a checkpoint write), the burned part stays, its units free, and it
  rolls back to the fraction it started the segment with.  Its ``k``-th
  kill (0-based, counted over the run) retries it on the same node after
  ``min(retry_base_s * retry_mult ** k, retry_cap_s)``, owing
  ``restart_time`` (the elastic settings', else the fault section's) at
  its next launch; at ``max_retries`` kills it is lost instead.  A retry
  re-enters the node's queue and decides if a unit is free.
* The end.  A replay stops where no work event is pending and no job
  waits, with the node timeline left in the heap.  COMPLETE-time
  migration (``elastic.migrate``) is out of scope: the reference refuses
  it.

Besides the records, a replay keeps the float64 Eq. (1) score of every
non-empty action a node decision takes (``decision_scores``), keyed by
node, time and the launches it made, so that the check can hold the
kernel's answered scores to this enumeration and not to operands that the
program prepared.
"""
from __future__ import annotations

import hashlib
import heapq
import itertools
import math
import random
from typing import Dict, List, Optional, Sequence, Tuple

ARRIVAL, COMPLETE, PREEMPT, RESUME = 0, 1, 2, 3
NODE_FAIL, NODE_RECOVER, JOB_FAIL, RETRY = 5, 6, 7, 8  # 4, MIGRATE, is refused
TIMELINE = frozenset((NODE_FAIL, NODE_RECOVER))


def fault_stream(seed: int, key: str) -> random.Random:
    digest = hashlib.sha256(f"{seed}:{key}".encode()).digest()
    return random.Random(int.from_bytes(digest[:8], "big"))


def exp_draw(rng: random.Random, mean: float) -> float:
    u = rng.random()
    while u <= 1e-12:
        u = rng.random()
    return -mean * math.log(u)


class Placement:
    """Contiguous units homed in isolation domains; domain-spreading first
    fit: the start whose least-occupied overlapped domain hosts the fewest
    jobs, then the lowest start."""

    def __init__(self, units: int, domains: int, free=None, domain_jobs=None):
        self.units = units
        self.domains = domains
        self.free = list(free) if free is not None else [True] * units
        self.domain_jobs = (
            list(domain_jobs) if domain_jobs else [0] * domains
        )
        self.dead = [False] * units
        self.dead_n = 0

    def mark_dead(self, ids) -> None:
        for u in ids:
            assert self.free[u] and not self.dead[u], u
            self.free[u] = False
            self.dead[u] = True
            self.dead_n += 1

    def revive(self, ids) -> None:
        for u in ids:
            assert self.dead[u], u
            self.dead[u] = False
            self.free[u] = True
            self.dead_n -= 1

    def free_count(self) -> int:
        return sum(1 for x in self.free if x)

    def occupied(self) -> int:
        return sum(1 for c in self.domain_jobs if c)

    def _dom(self, u: int) -> int:
        return u * self.domains // self.units

    def allocate(self, g: int):
        runs = []
        i = 0
        while i < self.units:
            if self.free[i]:
                j = i
                while j < self.units and self.free[j]:
                    j += 1
                runs.append((i, j - i))
                i = j
            else:
                i += 1
        best = None
        for start, length in runs:
            for s in range(start, start + length - g + 1):
                lo, hi = self._dom(s), self._dom(s + g - 1)
                home = min(range(lo, hi + 1),
                           key=lambda d: (self.domain_jobs[d], d))
                key = (self.domain_jobs[home], s)
                if best is None or key < best[0]:
                    best = (key, s, home)
                if self.domain_jobs[home] == 0:
                    break
            if best is not None and best[0][0] == 0:
                break
        if best is None:
            return None
        _, s, home = best
        ids = tuple(range(s, s + g))
        for u in ids:
            self.free[u] = False
        self.domain_jobs[home] += 1
        return ids, home

    def release(self, ids, home: int) -> None:
        for u in ids:
            self.free[u] = True
        self.domain_jobs[home] -= 1


def placeable(free, domain_jobs, domains: int, counts) -> bool:
    p = Placement(len(free), domains, free, domain_jobs)
    return all(p.allocate(g) is not None for g in sorted(counts, reverse=True))


class Profile:
    """One application on one hardware type."""

    def __init__(self, d: dict):
        self.runtime = {int(k): float(v) for k, v in d["runtime"].items()}
        self.power = {int(k): float(v) for k, v in d["busy_power"].items()}
        self.util = {int(k): float(v) for k, v in d.get("dram_util", {}).items()}
        self.ft = {int(k): float(v) for k, v in d.get("freq_time", {}).items()}
        self.fp = {int(k): float(v) for k, v in d.get("freq_power", {}).items()}
        self.levels = sorted(self.ft) if self.ft else [0]

    def runtime_at(self, g: int, f: int) -> float:
        return self.runtime[g] * self.ft[f] if self.ft else self.runtime[g]

    def power_at(self, g: int, f: int) -> float:
        return self.power[g] * self.fp[f] if self.fp else self.power[g]

    def modes(self, tau: float) -> List[Tuple[int, int, float, float]]:
        """(g, f, t_norm, e_norm) of the profiled, tau-filtered modes, in
        (g, f) order."""
        t_hat, p_hat = {}, {}
        for g in sorted(self.runtime):
            u = self.util.get(g)
            t_rel = 1.0 / (u * g) if u else self.runtime[g]
            if len(self.levels) == 1:
                t_hat[(g, 0)] = t_rel * 1.0
                p_hat[(g, 0)] = self.power[g] * 1.0
            else:
                for f in self.levels:
                    t_hat[(g, f)] = t_rel * self.ft[f] * 1.0
                    p_hat[(g, f)] = self.power_at(g, f) * 1.0
        t_min = min(t_hat.values())
        e_raw = {k: p_hat[k] * (t_hat[k] / t_min) for k in t_hat}
        e_min = min(e_raw.values())
        out = [(g, f, t_hat[(g, f)] / t_min, e_raw[(g, f)] / e_min)
               for g, f in sorted(t_hat)]
        best = min(m[2] for m in out)
        return [m for m in out if m[2] <= (1.0 + tau) * best]


class Seg:
    """One running segment of a job."""

    __slots__ = ("job", "g", "f", "units", "home", "start", "end", "power",
                 "frac0", "restart", "preempted", "failed", "frac_ckpt", "rec")

    def __init__(self, job, g, f, units, home, start, end, power, frac0,
                 restart, rec):
        self.job, self.g, self.f = job, g, f
        self.units, self.home = units, home
        self.start, self.end, self.power = start, end, power
        self.frac0, self.restart = frac0, restart
        self.preempted = False
        self.failed = False
        self.frac_ckpt = 0.0
        self.rec = rec

    def frac_at(self, t: float) -> float:
        useful = self.end - self.start - self.restart
        if useful <= 0.0:
            return 1.0
        el = min(max(t - self.start - self.restart, 0.0), useful)
        return self.frac0 + (1.0 - self.frac0) * el / useful


class Node:
    def __init__(self, name: str, units: int, domains: int, idle_w: float,
                 apps: Dict[str, Profile]):
        self.name = name
        self.units, self.domains, self.idle_w = units, domains, idle_w
        self.apps = apps
        self.pl = Placement(units, domains)
        self.waiting: List[str] = []
        self.running: List[Seg] = []
        self.records: List[list] = []
        self.t = 0.0
        self.busy = 0.0
        self.idle_us = 0.0
        self.progress: Dict[str, float] = {}
        self.restart_due: set = set()
        self.preempts: Dict[str, int] = {}
        self.segments: Dict[str, int] = {}

    def advance(self, t: float) -> None:
        self.idle_us += self.pl.free_count() * (t - self.t)
        self.t = t

    def alive(self) -> int:
        return self.units - self.pl.dead_n


class Reference:
    """Replays one arrival stream over one fleet; see the module docstring.
    After ``run``, ``counts`` tallies the fault plane's events (node
    failures, crashes, kills, retries, lost jobs, ...)."""

    def __init__(self, nodes: Sequence[dict], profiles: Dict[str, Dict[str, dict]],
                 sched: dict, elastic: Optional[dict],
                 faults: Optional[dict] = None):
        self.sched = sched
        self.elastic = elastic
        if elastic is not None and elastic["migrate"]:
            raise ValueError("the reference does not hold migration")
        # the loop's elastic phase runs only with resizing on
        self.resize = elastic if elastic is not None and elastic["resize"] else None
        on = faults is not None and (faults["node_mtbf_s"] > 0
                                     or faults["job_mtbf_s"] > 0
                                     or faults["straggler_prob"] > 0)
        self.faults = faults if on else None
        # segments and progress are tracked under either plane
        self.track = elastic is not None or self.faults is not None
        self.restart_time = (elastic["restart_time"] if elastic is not None
                             else faults["restart_time"] if self.faults else 0.0)
        self.nodes: List[Node] = []
        tables: Dict[str, Dict[str, Profile]] = {
            chip: {a: Profile(p) for a, p in apps.items()}
            for chip, apps in profiles.items()
        }
        for n in nodes:
            self.nodes.append(Node(n["name"], n["units"], n["domains"],
                                   n["idle_w"], tables[n["chip"]]))
        self.by_name = {n.name: n for n in self.nodes}
        self.rank_order = sorted(range(len(self.nodes)),
                                 key=lambda i: self.nodes[i].name)
        self._modes: Dict[Tuple[int, str], list] = {}
        # drain-proxy accumulators and per-(node, app) tables
        N = len(self.nodes)
        self.sum_end_g = [0.0] * N
        self.sum_g = [0.0] * N
        self.wait_us = [0.0] * N
        self.n_run = [0] * N
        self.n_wait = [0] * N
        self.index = {n.name: i for i, n in enumerate(self.nodes)}
        self.app_of: Dict[str, str] = {}

    # -- tables ---------------------------------------------------------------

    def _app_tables(self, i: int, app: str, limit: int):
        node = self.nodes[i]
        prof = node.apps.get(app)
        if prof is None:
            return None
        counts = [g for g in sorted(prof.runtime) if g <= limit]
        if not counts:
            return None
        min_us = min(prof.runtime_at(g, f) * g for g in counts
                     for f in prof.levels)
        e, t = min((prof.runtime_at(g, f) * prof.power_at(g, f),
                    prof.runtime_at(g, f)) for g in counts for f in prof.levels)
        return min_us, e, t

    def modes(self, i: int, job: str):
        app = self.app_of[job]
        key = (id(self.nodes[i].apps), app)
        m = self._modes.get(key)
        if m is None:
            m = self._modes[key] = self.nodes[i].apps[app].modes(
                self.sched["tau"])
        return m

    # -- Eq. (1) ------------------------------------------------------------

    def score(self, modes, g_free: int, M: int) -> float:
        lam, lam_f = self.sched["lam"], self.sched["lam_f"]
        tot = sum(m[0] for m in modes)
        r = sum(m[3] - 1.0 for m in modes) / len(modes) if modes else 0.0
        s = r + lam * ((g_free - tot) / M)
        if lam_f:
            s += lam_f * (sum(m[1] for m in modes) / len(modes) if modes else 0.0)
        return s

    def enumerate(self, windows, free, domain_jobs, domains, g_free, M):
        """[(score, ((pos, mode), ...))] of every feasible action, the
        empty action first, in enumeration order."""
        k_avail = domains - sum(1 for c in domain_jobs if c)
        out = [(self.score((), g_free, M), ())]
        if k_avail <= 0 or not windows:
            return out
        for size in range(1, min(k_avail, len(windows)) + 1):
            for combo in itertools.combinations(range(len(windows)), size):
                for ms in itertools.product(*[windows[p] for p in combo]):
                    counts = [m[0] for m in ms]
                    if sum(counts) > g_free:
                        continue
                    if not placeable(free, domain_jobs, domains, counts):
                        continue
                    out.append((self.score(ms, g_free, M), tuple(zip(combo, ms))))
        return out

    @staticmethod
    def best(scored, nonempty: bool = False):
        """(score, action) of the least score, then the most units, then
        the first in enumeration order."""
        best = None
        for s, a in scored:
            if nonempty and not a:
                continue
            key = (s, -sum(m[0] for _, m in a))
            if best is None or key < best[0]:
                best = (key, s, a)
        return None if best is None else best[1:]

    # -- node decisions ----------------------------------------------------------

    def decide(self, i: int) -> None:
        node = self.nodes[i]
        w = self.sched["window"]
        jobs = node.waiting[:w] if w else list(node.waiting)
        free_units = node.pl.free_count()
        if not jobs or node.domains - node.pl.occupied() <= 0 or free_units <= 0:
            return
        cands = [(j, self.modes(i, j)) for j in jobs]
        cands = [c for c in cands if c[1]]
        if not cands:
            return
        scored = self.enumerate([m for _, m in cands], node.pl.free,
                                node.pl.domain_jobs, node.domains,
                                free_units, node.alive())
        score, action = self.best(scored)
        if not action and not node.running:
            score, action = self.best(scored, nonempty=True) or (score, action)
        if action:
            key = tuple(sorted((cands[pos][0], m[0], m[1]) for pos, m in action))
            self.decision_scores[(node.name, node.t, key)] = score
        for pos, m in sorted(action, key=lambda pm: (-pm[1][0], pm[0])):
            self.launch(i, cands[pos][0], m[0], m[1])

    def launch(self, i: int, job: str, g: int, f: int) -> None:
        node = self.nodes[i]
        prof = node.apps[self.app_of[job]]
        units, home = node.pl.allocate(g)
        frac0, restart, segment = 0.0, 0.0, 0
        if self.track:
            frac0 = node.progress.pop(job, 0.0)
            if job in node.restart_due:
                node.restart_due.discard(job)
                restart = self.restart_time
            segment = node.segments.get(job, 0)
            node.segments[job] = segment + 1
        factor = 1.0
        if self.faults is not None:
            factor *= self.straggler(job, segment)
        solo = prof.runtime_at(g, f)
        if frac0 == 0.0 and restart == 0.0:
            dur = solo * factor
        else:
            dur = restart + (1.0 - frac0) * solo * factor
        power = prof.power_at(g, f)
        node.waiting.remove(job)
        rec = [job, node.name, g, f, node.t, node.t + dur, "run", segment]
        seg = Seg(job, g, f, units, home, node.t, node.t + dur, power, frac0,
                  restart, rec)
        node.running.append(seg)
        node.busy += power * dur
        node.records.append(rec)
        self.launches.append((job, node.name, g, f, node.t))
        # drain proxy
        self.wait_us[i] -= self._min_us(i, self.app_of[job])
        self.n_wait[i] -= 1
        if self.n_wait[i] == 0:
            self.wait_us[i] = 0.0
        self.sum_end_g[i] += seg.end * g
        self.sum_g[i] += g
        self.n_run[i] += 1
        self.push(seg.end, COMPLETE, (i, seg))
        if self.faults is not None:
            t_crash = seg.start + self.crash_offset(job, segment)
            if t_crash < seg.end:
                self.push(t_crash, JOB_FAIL, (i, seg))

    def _min_us(self, i: int, app: str) -> float:
        """The app's cheapest busy unit-seconds on node ``i`` as it stands
        (0.0 where no mode fits its alive units)."""
        tab = self._tab[i].get(app)
        return tab[0] if tab is not None else 0.0

    def _unbook(self, i: int, end: float, g: int) -> None:
        self.sum_end_g[i] -= end * g
        self.sum_g[i] -= g
        self.n_run[i] -= 1
        if self.n_run[i] == 0:
            self.sum_end_g[i] = 0.0
            self.sum_g[i] = 0.0

    def _enqueue(self, i: int, job: str) -> None:
        self.nodes[i].waiting.append(job)
        self.wait_us[i] += self._min_us(i, self.app_of[job])
        self.n_wait[i] += 1

    def _refit(self, i: int) -> None:
        """Node ``i``'s routing tables for its alive units, after a failure
        or a repair, and its waiting work re-summed under them."""
        node = self.nodes[i]
        alive = node.alive()
        row = {}
        for a in self.apps:
            tab = self._app_tables(i, a, alive)
            if tab is not None:
                row[a] = tab
        self._tab[i] = row
        self.div[i] = float(max(alive, 1))
        self.wait_us[i] = sum(self._min_us(i, self.app_of[j])
                              for j in node.waiting)

    # -- the fault plane -----------------------------------------------------

    def next_cycle(self, i: int):
        """(up, down, units lost) of node ``i``'s next failure."""
        node, cfg = self.nodes[i], self.faults
        rng = self.node_rng.get(i)
        if rng is None:
            rng = self.node_rng[i] = fault_stream(cfg["seed"], f"node:{node.name}")
        up = exp_draw(rng, cfg["node_mtbf_s"])
        down = exp_draw(rng, cfg["node_mttr_s"])
        if rng.random() < cfg["degrade_frac"]:
            k = min(cfg["degrade_units"], node.units)
        else:
            k = node.units
        return up, down, k

    def crash_offset(self, job: str, segment: int) -> float:
        cfg = self.faults
        if cfg["job_mtbf_s"] <= 0:
            return math.inf
        rng = fault_stream(cfg["seed"], f"job:{job}:{segment}")
        return exp_draw(rng, cfg["job_mtbf_s"])

    def straggler(self, job: str, segment: int) -> float:
        cfg = self.faults
        if cfg["straggler_prob"] <= 0:
            return 1.0
        rng = fault_stream(cfg["seed"], f"straggle:{job}:{segment}")
        if rng.random() < cfg["straggler_prob"]:
            self.counts["stragglers"] += 1
            return cfg["straggler_factor"]
        return 1.0

    def retry_delay(self, k: int) -> float:
        cfg = self.faults
        return min(cfg["retry_base_s"] * cfg["retry_mult"] ** k,
                   cfg["retry_cap_s"])

    def kill(self, i: int, seg: Seg, t: float) -> None:
        """A crash or a node failure kills ``seg`` at ``t``; the job retries
        on this node after its backoff, or is lost."""
        node = self.nodes[i]
        old_end = seg.end
        node.advance(t)
        if seg.preempted:  # the checkpoint write never lands
            self.counts["ckpt_kills"] += 1
            scale = self.elastic["ckpt_power_scale"] if self.elastic else 1.0
            refund = seg.power * scale * (seg.end - t)
        else:
            refund = seg.power * (seg.end - t)
        node.busy -= refund
        seg.rec[5] = t
        seg.rec[6] = "fail"
        node.progress[seg.job] = seg.frac0  # back to its last checkpoint
        seg.failed = True
        seg.end = t
        node.running.remove(seg)
        node.pl.release(seg.units, seg.home)
        node.restart_due.add(seg.job)
        self._unbook(i, old_end, seg.g)
        self.counts["kills"] += 1
        k = self.retries.get(seg.job, 0)
        if k >= self.faults["max_retries"]:
            node.progress.pop(seg.job, None)
            node.restart_due.discard(seg.job)
            self.lost.append(seg.job)
            self.counts["lost"] += 1
            return
        self.retries[seg.job] = k + 1
        self.push(t + self.retry_delay(k), RETRY, (i, seg.job))

    def node_fail(self, i: int, k: int, down: float, t: float) -> None:
        node = self.nodes[i]
        node.advance(t)
        self.counts["node_failures"] += 1
        alive = [u for u in range(node.units) if not node.pl.dead[u]]
        victims = set(alive[-k:]) if k < len(alive) else set(alive)
        if len(victims) < len(alive):
            self.counts["partial_failures"] += 1
        for seg in [s for s in node.running if set(s.units) & victims]:
            self.kill(i, seg, t)
        node.pl.mark_dead(sorted(victims))
        self._refit(i)
        if node.waiting and node.pl.free_count() > 0:
            self.decide(i)
        self.push(t + down, NODE_RECOVER, (i, sorted(victims)))

    def node_recover(self, i: int, ids, t: float) -> None:
        node = self.nodes[i]
        node.advance(t)
        node.pl.revive(ids)
        self._refit(i)
        if node.waiting:
            self.decide(i)
        up, down, k = self.next_cycle(i)
        self.push(t + up, NODE_FAIL, (i, k, down))

    # -- elastic resizing --------------------------------------------------

    def try_resize(self, i: int, t: float) -> None:
        node, cfg = self.nodes[i], self.resize
        free_units = node.pl.free_count()
        if free_units <= 0 or not node.running:
            return
        overhead = cfg["ckpt_time"] + cfg["restart_time"]
        best = None
        for seg in node.running:
            if seg.preempted or seg.frac_at(node.t) >= 1.0:
                continue
            rem_t = seg.end - node.t
            useful_rem = seg.end - max(node.t, seg.start + seg.restart)
            if useful_rem <= overhead + cfg["min_gain_s"]:
                continue
            modes = self.modes(i, seg.job)
            if len(modes) < 2:
                continue
            cur = next((m for m in modes if (m[0], m[1]) == (seg.g, seg.f)), None)
            if cur is None:
                continue
            free = list(node.pl.free)
            for u in seg.units:
                free[u] = True
            occ = list(node.pl.domain_jobs)
            if occ[seg.home] > 0:
                occ[seg.home] -= 1
            scored = self.enumerate([modes], free, occ, node.domains,
                                    free_units + seg.g, node.alive())
            pick = None
            for s, a in scored:
                if not a:
                    continue
                m = a[0][1]
                moved = (m[0], m[1]) != (seg.g, seg.f)
                key = (s + cfg["switch_cost"] if moved else s, -m[0])
                if pick is None or key < pick[0]:
                    pick = (key, m)
            if pick is None:
                continue
            m = pick[1]
            if (m[0], m[1]) == (seg.g, seg.f):
                continue
            gain = rem_t - (overhead + useful_rem * (m[2] / cur[2]))
            if gain <= cfg["min_gain_s"]:
                continue
            if best is None or gain > best[0]:
                best = (gain, seg)
        if best is None:
            return
        seg = best[1]
        if node.preempts.get(seg.job, 0) >= cfg["max_preempts"]:
            return
        if seg.end - t <= overhead:
            return
        frac = seg.frac_at(t)
        ck_end = t + cfg["ckpt_time"]
        ck_e = seg.power * cfg["ckpt_power_scale"] * cfg["ckpt_time"]
        node.busy -= seg.power * (seg.end - t)
        node.busy += ck_e
        seg.rec[5] = ck_end
        seg.rec[6] = "ckpt"
        seg.preempted = True
        seg.frac_ckpt = frac
        old_end = seg.end
        seg.end = ck_end
        node.preempts[seg.job] = node.preempts.get(seg.job, 0) + 1
        self.sum_end_g[i] += (ck_end - old_end) * seg.g
        self.push(ck_end, PREEMPT, (i, seg))

    # -- the event loop ----------------------------------------------------------

    def push(self, t: float, kind: int, payload) -> None:
        heapq.heappush(self.heap, (t, kind, self.seq, payload))
        self.seq += 1
        if kind not in TIMELINE:
            self.work += 1

    def pop(self):
        t, kind, _, payload = heapq.heappop(self.heap)
        if kind not in TIMELINE:
            self.work -= 1
        return t, kind, payload

    def idle(self) -> bool:
        """Only the node timeline is left: no work event, no waiting job."""
        return (self.faults is not None and self.work == 0
                and not any(n.waiting for n in self.nodes))

    def route(self, job: str, app: str, t: float) -> Optional[int]:
        """The node the arrival joins, or None when no node fits it now
        and it comes back ``retry_base_s`` later."""
        best = None
        for i in self.rank_order:
            tab = self._tab[i].get(app)
            if tab is None:
                continue
            _, e, tb = tab
            out = (max(self.sum_end_g[i] - t * self.sum_g[i], 0.0)
                   + self.wait_us[i]) / self.div[i]
            s = e * (out + tb) / tb
            if best is None or s < best[0]:
                best = (s, i)
        if best is None:
            if self.faults is not None and any(
                    self._app_tables(i, app, n.units) is not None
                    for i, n in enumerate(self.nodes)):
                self.counts["held"] += 1
                self.push(t + self.faults["retry_base_s"], ARRIVAL, (job, app))
                return None
            raise ValueError(f"no node can host {app}")
        i = best[1]
        self.nodes[i].advance(t)
        self._enqueue(i, job)
        return i

    def run(self, arrivals: Sequence[Tuple[float, str, str]]):
        """Replay ``(t, job, app)`` arrivals (t > 0) to the drain, or under
        faults until only the node timeline is left.  Returns (records,
        total energy, launches); a record is ``(job, node, g, f, start,
        end, kind, segment)``."""
        self.heap: list = []
        self.seq = 0
        self.work = 0
        self.launches: List[tuple] = []
        self.decision_scores: Dict[tuple, float] = {}
        self.retries: Dict[str, int] = {}
        self.lost: List[str] = []
        self.node_rng: Dict[int, random.Random] = {}
        self.counts = dict.fromkeys(
            ("node_failures", "partial_failures", "crashes", "kills",
             "ckpt_kills", "retries", "retry_on_dead", "lost", "stragglers",
             "held"), 0)
        self.apps = sorted({a for _, _, a in arrivals})
        self.div = [float(n.units) for n in self.nodes]
        self._tab = []
        for i, n in enumerate(self.nodes):
            row = {}
            for a in self.apps:
                tab = self._app_tables(i, a, n.units)
                if tab is not None:
                    row[a] = tab
            self._tab.append(row)
        for t, job, app in sorted(arrivals, key=lambda a: a[0]):
            if t <= 0.0:
                raise ValueError("reference arrivals must come after t = 0")
            self.app_of[job] = app
            self.push(t, ARRIVAL, (job, app))
        if self.faults is not None and self.faults["node_mtbf_s"] > 0:
            for i in range(len(self.nodes)):
                up, down, k = self.next_cycle(i)
                self.push(up, NODE_FAIL, (i, k, down))
        cfg = self.resize
        self.instants = {0.0}  # the loop's opening pass at t = 0 is one
        while self.heap and not self.idle():
            t, kind, payload = self.pop()
            self.instants.add(t)
            if kind == ARRIVAL:
                touched = [self.route(*payload, t)]
                while self.heap and self.heap[0][0] == t and self.heap[0][1] == ARRIVAL:
                    i = self.route(*self.pop()[2], t)
                    if i not in touched:
                        touched.append(i)
                for i in touched:
                    if i is not None:
                        self.decide(i)
            elif kind == COMPLETE:
                i, seg = payload
                if seg.preempted or seg.failed:
                    continue
                node = self.nodes[i]
                node.advance(seg.end)
                node.running.remove(seg)
                node.pl.release(seg.units, seg.home)
                self._unbook(i, seg.end, seg.g)
                if cfg is None:
                    if node.waiting:
                        self.decide(i)
                else:
                    if cfg["resize_before_backfill"]:
                        self.try_resize(i, t)
                    if node.waiting:
                        self.decide(i)
                    if not cfg["resize_before_backfill"]:
                        self.try_resize(i, t)
            elif kind == PREEMPT:
                i, seg = payload
                if seg.failed:
                    continue
                node = self.nodes[i]
                node.advance(t)
                node.running.remove(seg)
                node.pl.release(seg.units, seg.home)
                node.progress[seg.job] = seg.frac_ckpt
                node.restart_due.add(seg.job)
                self._unbook(i, seg.end, seg.g)
                self.push(t, RESUME, (i, seg.job))
            elif kind == RESUME:
                i, job = payload
                self.nodes[i].advance(t)
                self._enqueue(i, job)
                self.decide(i)
            elif kind == JOB_FAIL:
                i, seg = payload
                node = self.nodes[i]
                if seg.preempted or seg.failed or seg not in node.running:
                    continue  # checkpointing, killed or done before it hit
                self.counts["crashes"] += 1
                self.kill(i, seg, t)
                if node.waiting and node.pl.free_count() > 0:
                    self.decide(i)
            elif kind == NODE_FAIL:
                self.node_fail(*payload, t)
            elif kind == NODE_RECOVER:
                self.node_recover(*payload, t)
            elif kind == RETRY:
                i, job = payload
                node = self.nodes[i]
                node.advance(t)
                self._enqueue(i, job)
                self.counts["retries"] += 1
                if node.pl.dead_n >= node.units:
                    self.counts["retry_on_dead"] += 1  # waits for the repair
                elif node.pl.free_count() > 0:
                    self.decide(i)
            else:
                raise ValueError(f"the reference has no event kind {kind}")
        stuck = [n.name for n in self.nodes if n.waiting]
        if stuck:
            raise RuntimeError(f"reference drained with waiting jobs on {stuck}")
        makespan = max(n.t for n in self.nodes)
        busy = sum(n.busy for n in self.nodes)
        idle = sum(n.idle_us * n.idle_w for n in self.nodes) + sum(
            (makespan - n.t) * n.units * n.idle_w for n in self.nodes)
        records = [tuple(r) for n in self.nodes for r in n.records]
        return records, busy + idle, self.launches
