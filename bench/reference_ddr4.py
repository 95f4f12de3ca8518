"""The plain reference of a bank-grouped (DDR4) memory system, in pure
Python.

It serves the same request streams as the simulator under test, one
request at a time, with every cycle stamp in exact Python integers, and
returns the statistics a study reports for one grid point.  It imports
nothing of the program: the clock, every timing, the geometry, the HCRAC
and the lowered timings come from the configuration file
(``bench/configs/ddr4_*.json``).

Model, as the configuration states it (JEDEC JESD79-4 DDR4-2400R under
the thesis's eight-core system, arXiv:1609.07234 Table 5.1):

* per-core issue: a request issues ``gap`` cycles after its core's
  previous one, no earlier than the completion of the request one MSHR
  ring slot back, and — if it depends on its predecessor — no earlier
  than the predecessor's completion.  The core whose next request issues
  earliest is admitted next (lowest core index on a tie);
* the controller keeps a window of up to ``window`` admitted requests
  (1 for the in-order tier) and serves row hits first, then the oldest
  admission; the FR-FCFS tier also enforces, per rank, tRRD and tFAW
  and, per bank group of the rank, tRRD_L between ACTs;
* bank groups: bank ``b`` of a rank is in group ``b mod n_bank_groups``
  (consecutive bank ids in different groups, an assumed mapping).  A
  RD/WR issues no earlier than tCCD_S after the channel's newest RD/WR
  and tCCD_L after the newest RD/WR to its rank's bank group, on both
  tiers;
* per bank: open row, PRE / ACT / RD-WR ready clocks, closed-row
  auto-precharge unless the core's next request to the bank hits the
  same row; per channel: command and data bus occupancy;
* rolling all-bank refresh, one REF per ``tREFI`` per bank, blocking the
  bank for ``tRFC`` and closing its open row;
* the HCRAC: a 2-way set-associative table of recently precharged rows,
  inserted on every PRE, looked up on every ACT, entries invalidated by
  the IIC/EC sweep (slot ``s`` swept at ``(s + 1) * C / k`` mod ``C``);
* timing selection per mechanism: LL-DRAM always lowered, ChargeCache
  lowered on an HCRAC hit (``MECHANISMS``).

Besides the DDR3 reference's counters it reports ``ccd_wait_cycles``
(cycles tCCD_S/tCCD_L pushed a measured RD/WR past every other rule) and
``rrd_l_wait_cycles`` (cycles tRRD_L pushed a measured ACT past tRRD and
tFAW).  ``run`` also returns the RLTL histogram when asked.
"""

from __future__ import annotations

import bisect

import numpy as np

#: the cycle horizon the simulated clocks stay below
INF = 2 ** 30
#: FR-FCFS selection key: a row miss sorts after every row hit
HIT_PENALTY = 1 << 26
#: ACT registers start here, far before any real cycle
NEG = -(2 ** 28)
#: tFAW is a rolling window of four ACTs per rank
FAW_DEPTH = 4
NO_ROW = -1

#: the stat counters a grid point reports
STAT_KEYS = ("n_req", "lat_sum", "acts", "acts_lowered", "hcrac_hits",
             "hcrac_lookups", "row_hits", "row_closed", "row_conflicts",
             "reads", "writes", "pres", "act_ras_sum", "refresh8ms_acts",
             "refs_issued", "ref_blocked_cycles", "ccd_wait_cycles",
             "rrd_l_wait_cycles")
#: upper edges (ms) of the RLTL histogram's buckets
RLTL_EDGES_MS = (0.125, 0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0)

#: the mechanisms the reference models, and so the ones a run compares
MECHANISMS = ("base", "chargecache", "lldram")


def ms_to_cycles(ms: float, cycle_ns: float) -> int:
    return int(round(ms * 1e6 / cycle_ns))


def point_params(cfg: dict, point: dict) -> dict:
    """The numbers one grid point runs with: the configuration's fixed
    values plus the point's mechanism, HCRAC capacity (entries in all)
    and caching duration."""
    ms = float(point.get("duration_ms", cfg["hcrac"]["duration_ms"]))
    low = cfg["lowered_by_duration_ms"][repr(ms)]
    return {
        "kind": point["mechanism"],
        "entries": int(point.get("entries", cfg["hcrac"]["entries"])),
        "ways": int(cfg["hcrac"]["ways"]),
        "caching": ms_to_cycles(ms, cfg["timing"]["cycle_ns"]),
        "low_rcd": int(low["tRCD"]),
        "low_ras": int(low["tRAS"]),
    }


def run(batch, cfg: dict, point: dict, rltl: bool = False) -> dict:
    """Simulate one grid point on ``batch`` (``gap/bank/row/is_write/dep/
    next_same [C, L]``, ``length [C]``); returns the stat counters,
    ``total_cycles``, ``core_end [C]``, the per-bank ``bank_acts`` /
    ``bank_act_ras_sum`` and, with ``rltl``, ``rltl_hist`` /
    ``rltl_total``."""
    T = cfg["timing"]
    G = cfg["geometry"]
    pp = point_params(cfg, point)
    kind = pp["kind"]
    if kind not in MECHANISMS:
        raise ValueError(f"the reference does not model {kind!r}")
    frfcfs = cfg["controller"] == "frfcfs"
    cap = int(cfg["window"]) if frfcfs else 1
    closed = cfg["row_policy"] == "closed"
    mshr = int(cfg["mshr"])
    tRCD, tRAS, tRP = T["tRCD"], T["tRAS"], T["tRP"]
    tCL, tCWL, tBL = T["tCL"], T["tCWL"], T["tBL"]
    tRTP, tWR, tRRD, tFAW = T["tRTP"], T["tWR"], T["tRRD"], T["tFAW"]
    tCCD_S, tCCD_L, tRRD_L = T["tCCD_S"], T["tCCD_L"], T["tRRD_L"]
    tREFI, tRFC = T["tREFI"], T["tRFC"]
    groups = T["n_refresh_groups"]
    retention = tREFI * groups
    ms8 = ms_to_cycles(8.0, T["cycle_ns"])
    n_banks = G["n_banks"]
    n_bg = G["n_bank_groups"]
    bpc = G["n_ranks"] * n_banks
    nb = G["n_channels"] * bpc
    n_rows = G["n_rows"]
    nch = G["n_channels"]

    low_rcd, low_ras = pp["low_rcd"], pp["low_ras"]
    lldram = kind == "lldram"
    hc_gate = kind == "chargecache"
    caching = pp["caching"]

    # --- HCRAC: flat [sets * ways] lists -----------------------------------
    ways = pp["ways"]
    n_sets = pp["entries"] // ways
    sweep = max(1, caching // pp["entries"])
    exact = bool(cfg["hcrac"]["exact_expiry"])
    h_tag = [-1] * (n_sets * ways)
    h_it = [0] * (n_sets * ways)
    h_lru = [-1] * (n_sets * ways)

    def alive(slot, itime, t):
        if exact:
            return t - itime <= caching
        phase = (slot + 1) * sweep
        return (t - phase) // caching == (itime - phase) // caching

    def hc_lookup(gid, t):
        base = (gid % n_sets) * ways
        hit = False
        for s in range(base, base + ways):
            if h_tag[s] == gid and alive(s, h_it[s], t):
                h_lru[s] = t
                hit = True
        return hit

    def hc_insert(gid, t):
        base = (gid % n_sets) * ways
        pick = inv = lru_pick = -1
        best = None
        for s in range(base, base + ways):
            valid = h_tag[s] != -1 and alive(s, h_it[s], t)
            if valid:
                if h_tag[s] == gid and pick < 0:
                    pick = s
                if best is None or h_lru[s] < best:
                    best, lru_pick = h_lru[s], s
            elif inv < 0:
                inv = s
        s = pick if pick >= 0 else (inv if inv >= 0 else lru_pick)
        h_tag[s], h_it[s], h_lru[s] = gid, t, t

    # --- streams -------------------------------------------------------------
    gap = np.asarray(batch.gap).tolist()
    dep = np.asarray(batch.dep).tolist()
    wr = np.asarray(batch.is_write).tolist()
    fbank = np.asarray(batch.bank).tolist()
    frow = np.asarray(batch.row).tolist()
    nsame = np.asarray(batch.next_same).tolist()
    length = [int(x) for x in np.asarray(batch.length)]
    C = len(length)
    n_req = sum(length)
    warmup = int(cfg["warmup_frac"] * n_req)

    # --- state ---------------------------------------------------------------
    ptrs = [0] * C
    last_issue = [0] * C
    ring = [[0] * mshr for _ in range(C)]
    ring_served = [[True] * mshr for _ in range(C)]
    yg_served = [True] * C
    yg_done = [0] * C
    core_end = [0] * C
    open_row = [NO_ROW] * nb
    ready_act = [0] * nb
    ready_rdwr = [0] * nb
    ready_pre = [0] * nb
    lp_gid = [-1] * nb
    lp_t = [0] * nb
    ref_k = [0] * nb
    last_ref_t = [0] * nb
    cmd_free = [0] * nch
    data_free = [0] * nch
    n_rank = nb // n_banks
    rank_last_act = [NEG] * n_rank
    faw = [[NEG] * FAW_DEPTH for _ in range(n_rank)]
    faw_ptr = [0] * n_rank
    # bank groups: newest RD/WR per channel and per (rank, group), newest
    # ACT per (rank, group); (rank r, group g) is slot r * n_banks + g
    last_cas = [-INF] * nch
    last_cas_bg = [-INF] * nb
    bg_last_act = [NEG] * nb
    st = dict.fromkeys(STAT_KEYS, 0)
    bank_acts = [0] * nb
    bank_ras = [0] * nb
    events = [] if rltl else None

    window = []   # [core, idx, bank, row, write, next_same, arrival, seq]
    now = 0
    seq = 0
    served = 0
    while served < n_req:
        # admission: fill the window from the per-core issue fronts
        while len(window) < cap:
            c_best, t_best = -1, INF
            for c in range(C):
                ptr = ptrs[c]
                if ptr >= length[c]:
                    continue
                pos = ptr % mshr
                d = dep[c][ptr]
                if not ring_served[c][pos] or (d and not yg_served[c]):
                    continue
                t = last_issue[c] + gap[c][ptr]
                if ring[c][pos] > t:
                    t = ring[c][pos]
                if d and yg_done[c] > t:
                    t = yg_done[c]
                if t < t_best:
                    c_best, t_best = c, t
            if c_best < 0 or not (t_best <= now or not window):
                break
            if not window:
                now = max(now, t_best)
            ptr = ptrs[c_best]
            window.append((c_best, ptr, fbank[c_best][ptr] % nb,
                           frow[c_best][ptr] % n_rows, wr[c_best][ptr],
                           nsame[c_best][ptr], t_best, seq))
            ptrs[c_best] = ptr + 1
            last_issue[c_best] = t_best
            yg_served[c_best] = False
            ring_served[c_best][ptr % mshr] = False
            seq += 1
        if not window:
            raise RuntimeError("reference controller deadlocked")

        # selection: row hits first, then the oldest admission
        if cap == 1:
            ent = window[0]
        else:
            ent, kbest = None, None
            for e in window:
                k = e[7] if open_row[e[2]] == e[3] else HIT_PENALTY + e[7]
                if kbest is None or k < kbest:
                    ent, kbest = e, k
        core, idx, b, row, write, ns, arr, _ = ent
        rank = b // n_banks
        slot = b - b % n_banks + (b % n_banks) % n_bg
        floor = floor_bg = 0
        if frfcfs:
            floor = max(rank_last_act[rank] + tRRD,
                        faw[rank][faw_ptr[rank]] + tFAW)
            floor_bg = max(floor, bg_last_act[slot] + tRRD_L)
        measure = served >= warmup
        ch = b // bpc

        # ---- one request through refresh / PRE / ACT / RD-WR ----
        t0 = max(arr, cmd_free[ch])
        ref_due = t0 // tREFI + 1
        n_pend = max(ref_due - ref_k[b], 0)
        do_ref = n_pend > 0
        busy0 = max(ready_act[b], ready_pre[b], ready_rdwr[b])
        ref_t = max((ref_due - 1) * tREFI, ready_pre[b])
        ref_done = ref_t + tRFC
        openr0 = open_row[b]
        ref_pre = do_ref and openr0 != NO_ROW
        openr = NO_ROW if do_ref else openr0
        if do_ref:
            r_act = max(ready_act[b], ref_done)
            r_pre = max(ready_pre[b], ref_done)
            r_rdwr = max(ready_rdwr[b], ref_done)
        else:
            r_act, r_pre, r_rdwr = ready_act[b], ready_pre[b], ready_rdwr[b]
        gid_ref = b * n_rows + (openr0 if ref_pre else 0)
        if ref_pre:
            if hc_gate:
                hc_insert(gid_ref, ref_t)
            if rltl:
                events.append((gid_ref, ref_t, 0))

        is_hit = openr == row
        is_closed = openr == NO_ROW
        is_conflict = not is_hit and not is_closed
        t_pre = max(t0, r_pre)
        gid_old = b * n_rows + (openr if is_conflict else 0)
        if is_conflict:
            if hc_gate:
                hc_insert(gid_old, t_pre)
            if rltl:
                events.append((gid_old, t_pre, 0))
        t_act = t_pre + tRP if is_conflict else max(t0, r_act)
        needs_act = not is_hit
        rrd_l_wait = 0
        if needs_act:
            t_act = max(t_act, floor)
            rrd_l_wait = max(floor_bg - t_act, 0)
            t_act += rrd_l_wait

        gid = b * n_rows + row
        cc_hit = hc_lookup(gid, t_act) and needs_act and hc_gate
        kw = ref_due - 1
        j_g = kw - ((kw - row % groups) % groups)
        new_last_ref_t = ref_t if do_ref else last_ref_t[b]
        if j_g >= 0:
            t_ref = new_last_ref_t if j_g == kw else j_g * tREFI
            tsr = max(t_act - t_ref, 0)
        else:
            tsr = (t_act - (row % groups) * tREFI) % retention

        rcd, ras = tRCD, tRAS
        if lldram or cc_hit:
            rcd, ras = low_rcd, low_ras
        lowered_used = needs_act and (rcd < tRCD or ras < tRAS)

        t_rdwr = max(t0, r_rdwr) if is_hit else t_act + rcd
        cas = tCWL if write else tCL
        if data_free[ch] - cas > t_rdwr:
            t_rdwr = data_free[ch] - cas
        # column-to-column spacing on the channel and in the bank group
        t_free = t_rdwr
        t_rdwr = max(t_rdwr, last_cas[ch] + tCCD_S,
                     last_cas_bg[slot] + tCCD_L)
        last_cas[ch] = last_cas_bg[slot] = t_rdwr
        done = t_rdwr + cas + tBL

        new_ready_rdwr = t_act + rcd if needs_act else r_rdwr
        after_rw = done + tWR if write else t_rdwr + tRTP
        new_ready_pre = max(t_act + ras if needs_act else r_pre, after_rw)
        auto_pre = closed and not ns
        if auto_pre:
            if hc_gate:
                hc_insert(gid, new_ready_pre)
            if rltl:
                events.append((gid, new_ready_pre, 0))
        if rltl and needs_act and measure:
            events.append((gid, t_act, 1))

        open_row[b] = NO_ROW if auto_pre else row
        ready_act[b] = (new_ready_pre + tRP if auto_pre
                        else (t_pre + tRP if is_conflict else r_act))
        ready_rdwr[b] = new_ready_rdwr
        ready_pre[b] = new_ready_pre
        cmd_free[ch] = (max(cmd_free[ch], arr) + 1 + needs_act
                        + is_conflict + auto_pre)
        data_free[ch] = done
        if auto_pre:
            lp_gid[b], lp_t[b] = gid, new_ready_pre
        elif is_conflict:
            lp_gid[b], lp_t[b] = gid_old, t_pre
        elif ref_pre:
            lp_gid[b], lp_t[b] = gid_ref, ref_t
        if do_ref:
            ref_k[b] = ref_due
        last_ref_t[b] = new_last_ref_t

        if measure:
            st["n_req"] += 1
            st["lat_sum"] += done - arr
            st["reads"] += not write
            st["writes"] += write
            st["row_hits"] += is_hit
            st["row_closed"] += is_closed
            st["row_conflicts"] += is_conflict
            st["pres"] += is_conflict + auto_pre
            st["refs_issued"] += n_pend
            st["ccd_wait_cycles"] += t_rdwr - t_free
            if needs_act:
                st["acts"] += 1
                st["acts_lowered"] += lowered_used
                st["hcrac_lookups"] += hc_gate
                st["hcrac_hits"] += cc_hit
                st["act_ras_sum"] += ras
                st["refresh8ms_acts"] += tsr < ms8
                st["rrd_l_wait_cycles"] += rrd_l_wait
                bank_acts[b] += 1
                bank_ras[b] += ras
            if do_ref:
                st["ref_blocked_cycles"] += max(ref_done - max(t0, busy0), 0)

        if needs_act and frfcfs:
            rank_last_act[rank] = max(rank_last_act[rank], t_act)
            faw[rank][faw_ptr[rank]] = t_act
            faw_ptr[rank] = (faw_ptr[rank] + 1) % FAW_DEPTH
            bg_last_act[slot] = max(bg_last_act[slot], t_act)

        pos = idx % mshr
        ring[core][pos] = done
        ring_served[core][pos] = True
        if done > core_end[core]:
            core_end[core] = done
        if idx == ptrs[core] - 1:   # the core's youngest admitted request
            yg_served[core] = True
            yg_done[core] = done
        window.remove(ent)
        now = max(now, cmd_free[ch])
        served += 1

    total = max(core_end)
    # REFs keep issuing on schedule until the last request completes
    st["refs_issued"] = (total // tREFI + 1) * nb
    out = dict(st)
    out["total_cycles"] = total
    out["core_end"] = core_end
    out["bank_acts"] = bank_acts
    out["bank_act_ras_sum"] = bank_ras
    if rltl:
        out["rltl_hist"], out["rltl_total"] = rltl_histogram(
            events, T["cycle_ns"])
    return out


def rltl_histogram(events, cycle_ns: float):
    """Match each measured ACT to its row's latest PRE: events sorted by
    (row id, cycle, PRE before ACT); an ACT right after a PRE of the same
    row has a valid interval, bucketed by ``RLTL_EDGES_MS``."""
    edges = [ms_to_cycles(e, cycle_ns) for e in RLTL_EDGES_MS]
    hist = [0] * (len(edges) + 1)
    total = 0
    prev = None
    for ev in sorted(events):
        if (ev[2] == 1 and prev is not None and prev[0] == ev[0]
                and prev[2] == 0):
            hist[bisect.bisect_left(edges, ev[1] - prev[1])] += 1
            total += 1
        prev = ev
    return hist, total
