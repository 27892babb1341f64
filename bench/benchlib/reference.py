"""Plain reference of what the association answer must be, in NumPy.

Written from the paper (HFEL, arXiv:2002.11343, Sec. II-IV), independent of
``src/``: it imports nothing of the program and reads only the scenario that
the benchmark itself generated.

* the eq.-(1)-(17) cost model and the Section-III constants (a, b, d, e, w);
* a group solver that finds the optimum of problem (18), which is convex:
  golden section over the common deadline T, at each T a root search for
  the bandwidth price, and at each price a per-device solve of the
  communication time (closed form where f sits at f_min, false position
  where it does not). It follows none of the program's solvers and
  none of its iteration counts. It runs in any NumPy float type: float64
  for the reference, bfloat16 for the control;
* the checks: reach (constraint 17e), placement gaps (a stable point leaves
  no device a transfer that lowers the two groups' cost by more than
  ``rel_tol`` of their sum), the optimality and feasibility of the (f, beta)
  a finalized answer reports, and its eq.-(17) cost.
"""

from __future__ import annotations

import math

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16
F64 = np.float64

_PHI = (math.sqrt(5.0) - 1.0) / 2.0
# iterations of the group solver: golden-section steps over the deadline,
# bisection then false-position steps of the bandwidth price, false-position
# steps of each device's communication time, and bisection steps of the
# least feasible deadline
SCHEDULE = {"n_golden": 28, "n_halve": 5, "n_price": 6, "n_bracket": 64,
            "n_root": 6}


class Model:
    """Per-scenario constants in float64."""

    def __init__(self, sc):
        dev, srv, lp = sc.dev, sc.srv, sc.lp
        g = lambda x: np.asarray(x, F64)                       # noqa: E731
        self.n, self.k = sc.n_devices, sc.n_servers
        self.local_iters = lp.mu * math.log(1.0 / lp.theta)
        self.edge_iters = (lp.delta * math.log(1.0 / lp.epsilon)
                           / (1.0 - lp.theta))
        self.lambda_e, self.lambda_t = lp.lambda_e, lp.lambda_t
        self.cycles = g(dev.cycles_per_iter)
        self.nats = g(dev.model_nats)
        self.power = g(dev.tx_power)
        self.gain = g(dev.channel_gain)
        self.alpha = g(dev.alpha)
        self.f_min, self.f_max = g(dev.f_min), g(dev.f_max)
        self.bw, self.noise = g(srv.bandwidth), g(srv.noise)
        cloud_t = g(srv.cloud_nats) / g(srv.cloud_rate)        # eq. (12)
        self.cloud_t = cloud_t
        self.cloud_e = g(srv.cloud_power) * cloud_t            # eq. (13)
        self.cloud = self.lambda_e * self.cloud_e + self.lambda_t * cloud_t
        self.active = np.asarray(sc.active_mask, bool)
        self.reach = np.asarray(sc.avail, bool) & self.active[None, :]

    def rate_per_hz(self, servers, devices):
        """B_i ln(1 + h p / N0): nats/s of a whole band, eq. (5)."""
        return self.bw[servers] * np.log1p(
            self.gain[devices] * self.power[devices] / self.noise[servers])

    def consts(self, servers, idx):
        """Problem-(18) constants of padded groups: (G, R) arrays and w."""
        s = np.asarray(servers)[:, None]
        eff = self.rate_per_hz(s, idx)
        i_it, l_it = self.edge_iters, self.local_iters
        return dict(
            a=self.lambda_e * i_it * self.nats[idx] * self.power[idx] / eff,
            b=self.lambda_e * i_it * l_it * 0.5 * self.alpha[idx]
            * self.cycles[idx],
            d=self.nats[idx] / eff,
            e=l_it * self.cycles[idx],
            f_min=self.f_min[idx], f_max=self.f_max[idx],
            w=np.full(len(servers), self.lambda_t * i_it))


def pack(groups):
    """[(server, device ids)] -> servers (G,), idx (G, R), mask (G, R)."""
    width = max([len(m) for _, m in groups] + [1])
    idx = np.zeros((len(groups), width), np.int64)
    mask = np.zeros((len(groups), width), bool)
    for g, (_, members) in enumerate(groups):
        idx[g, :len(members)] = members
        mask[g, :len(members)] = True
    return np.array([s for s, _ in groups], np.int64), idx, mask


def _objective(c, mask, f, beta, dt):
    one = dt(1.0)
    safe = np.where(mask, beta, one)
    per_sum = np.where(mask, c["a"] / safe + c["b"] * f * f, dt(0.0))
    per_max = np.where(mask, c["d"] / safe + c["e"] / f, dt(0.0))
    return np.sum(per_sum, axis=1) + c["w"] * np.max(per_max, axis=1)


def _false_position(rise, lo, hi, r_lo, r_hi, n_iter, dt, n_halve=0):
    """Root of ``rise``, increasing, on brackets [lo, hi] where it runs
    from r_lo <= 0 to r_hi >= 0: ``n_halve`` bisection steps, then false
    position with the Anderson-Bjorck weighting of the end that stays,
    elementwise over arrays."""
    one, half, two = dt(1.0), dt(0.5), dt(2.0)
    for _ in range(n_halve):
        mid = (lo + hi) / two
        r = rise(mid)
        up = r > 0
        r_lo, lo = np.where(up, r_lo, r), np.where(up, lo, mid)
        r_hi, hi = np.where(up, r, r_hi), np.where(up, mid, hi)

    def guess():
        width = r_hi - r_lo
        ok = width > 0
        x = np.where(ok, hi - r_hi * (hi - lo) / np.where(ok, width, one),
                     (lo + hi) / two)
        return np.clip(x, lo, hi)

    for _ in range(n_iter):
        x = guess()
        r = rise(x)
        up = r > 0
        keep = np.where(up, one - r / np.where(r_hi != 0, r_hi, one),
                        one - r / np.where(r_lo != 0, r_lo, one))
        keep = np.where(keep > 0, keep, half)
        r_lo, lo = np.where(up, r_lo * keep, r), np.where(up, lo, x)
        r_hi, hi = np.where(up, r, r_hi * keep), np.where(up, x, hi)
    return guess()


def _comm_time(c, T, nu, dt, n_root):
    """Per device, at deadline T and bandwidth price nu (both (G, 1)): the
    communication time u = d / beta that minimises

        h(u) = (a / d) u + nu d / u + b e^2 / min(e / f_min, T - u)^2

    over [d, T - e / f_max] (beta <= 1, f <= f_max, d / beta + e / f <= T).
    h is convex. Where f sits at f_min (u below u_c = T - e / f_min) its
    least point is closed-form, u = d sqrt(nu / a). Above u_c it solves
    nu d / u^2 = a / d + 2 b e^2 / (T - u)^3; in x = ln u, over the bracket
    [ln u_c, ln(T - e / f_max)], the logarithm of that equation is smooth
    and monotone (T - u stays at or above e / f_max), and false position
    finds its root."""
    a, b, d, e = c["a"], c["b"], c["d"], c["e"]
    two = dt(2.0)
    u_hi = T - e / c["f_max"]
    uc = np.clip(T - e / c["f_min"], d, u_hi)
    below = (T - e / c["f_min"] > d) & (a / d - nu * d / (uc * uc) >= 0)
    u_a = np.clip(d * np.sqrt(nu / a), d, uc)

    def rise(x):
        """Increasing in x; its root is the least point above u_c."""
        v = T - np.exp(x)
        return (np.log(a / d + two * b * e * e / (v * v * v)) + two * x
                - np.log(nu * d))

    lo, hi = np.log(uc), np.log(u_hi)
    r_lo, r_hi = rise(lo), rise(hi)
    x = _false_position(rise, lo, hi, r_lo, r_hi, n_root, dt)
    # h' >= 0 just above u_c: u stays at u_c; h' <= 0 up to u_hi: f_max
    u_b = np.where(r_lo >= 0, uc, np.where(r_hi <= 0, u_hi, np.exp(x)))
    return np.where(below, u_a, u_b)


def _alloc(c, T, nu, dt, n_root):
    u = _comm_time(c, T, nu, dt, n_root)
    beta = c["d"] / u
    f = np.clip(c["e"] / (T - u), c["f_min"], c["f_max"])
    return f, beta


def _price_for(c, T, beta, dt):
    """The bandwidth price at which a device takes share ``beta``."""
    u = c["d"] / beta
    tail = np.where(T - u < c["e"] / c["f_min"],
                    dt(2.0) * c["b"] * c["e"] ** 2 * c["d"] / (T - u) ** 3,
                    dt(0.0))
    return (c["a"] + tail) / (beta * beta)


def _fixed_deadline(c, mask, T, dt, sched):
    """Problem (18) with the deadline fixed at T (G,): the bandwidth price
    nu at which the shares sum to 1, found by false position on
    -ln(sum of shares) over ln nu (increasing). Shares t that sum to 1, each
    at least its device's least share, bracket it: at the least of the
    devices' prices for their t every share is at least t, at the largest
    at most t. The shares are scaled down to sum to at most 1."""
    Tc = T[:, None]
    big, small = dt(np.finfo(np.float32).max), dt(0.0)
    beta_lo = np.where(mask, c["d"] / (Tc - c["e"] / c["f_max"]), small)
    spare = dt(1.0) - np.sum(beta_lo, axis=1, keepdims=True)
    share = beta_lo + spare / np.sum(mask, axis=1, keepdims=True)
    price = _price_for(c, Tc, np.where(mask, share, dt(1.0)), dt)
    nu_lo = np.min(np.where(mask, price, big), axis=1)
    nu_hi = np.max(np.where(mask, price, small), axis=1)

    def rise(log_nu):
        _, beta = _alloc(c, Tc, np.exp(log_nu)[:, None], dt, sched["n_root"])
        return -np.log(np.sum(np.where(mask, beta, small), axis=1))

    lo, hi = np.log(nu_lo), np.log(np.maximum(nu_hi, nu_lo))
    log_nu = _false_position(rise, lo, hi, rise(lo), rise(hi),
                             sched["n_price"], dt, sched["n_halve"])
    f, beta = _alloc(c, Tc, np.exp(log_nu)[:, None], dt, sched["n_root"])
    total = np.sum(np.where(mask, beta, small), axis=1, keepdims=True)
    return f, beta / np.maximum(total, dt(1.0))


def _deadline_range(c, mask, dt, n_bisect):
    """[T_lo, T_hi] holding the optimal deadline. T_lo: the least deadline
    at which every device can finish at f_max with the shares summing to 1.
    T_hi: the deadline that the unconstrained optimum (f = f_min, beta
    proportional to sqrt(a)) needs; past it the objective only grows."""
    e_f = c["e"] / c["f_max"]
    lo = np.max(np.where(mask, e_f + c["d"], dt(0.0)), axis=1)
    hi = (np.max(np.where(mask, e_f, dt(0.0)), axis=1)
          + np.sum(np.where(mask, c["d"], dt(0.0)), axis=1))
    for _ in range(n_bisect):
        mid = (lo + hi) / dt(2.0)
        slack = mid[:, None] - e_f
        need = np.where(mask & (slack > 0),
                        c["d"] / np.where(slack > 0, slack, dt(1.0)),
                        np.where(mask, dt(np.inf), dt(0.0)))
        ok = np.sum(need, axis=1) <= 1
        lo, hi = np.where(ok, lo, mid), np.where(ok, mid, hi)
    root_a = np.where(mask, np.sqrt(c["a"]), dt(0.0))
    beta_a = root_a / np.sum(root_a, axis=1, keepdims=True)
    t_free = np.max(np.where(mask, c["d"] / np.where(mask, beta_a, dt(1.0))
                             + c["e"] / c["f_min"], dt(0.0)), axis=1)
    return hi, np.maximum(t_free, hi * dt(1.0 + 1e-6))


def solve_groups(model: Model, groups, dt=F64, schedule=None):
    """The optimum of problem (18) for each (server, members) group, plus
    the server's cloud constant when the group is not empty.

    (18) is convex. At a fixed common deadline T it separates by device
    once the shares' sum is priced at nu; the price is bisected until the
    shares sum to 1, and the least objective over T is found by golden
    section between the least feasible deadline and the deadline of the
    unconstrained optimum, over which the objective is convex. Iteration
    counts are ``SCHEDULE``'s unless ``schedule`` gives others.
    Returns (cost (G,), f (G, R), beta (G, R), idx, mask). An empty group
    costs 0; its entries, like padded slots, are not read."""
    with np.errstate(all="ignore"):
        return _solve_groups(model, groups, dt, schedule)


def _solve_groups(model: Model, groups, dt, schedule):
    sched = SCHEDULE if schedule is None else schedule
    servers, idx, mask = pack(groups)
    c = {k: np.asarray(v, dt) for k, v in model.consts(servers, idx).items()}
    # padded slots take their group's first member's constants, so that
    # every per-device formula stays finite; sums and maxima mask them
    for k in ("a", "b", "d", "e", "f_min", "f_max"):
        c[k] = np.where(mask, c[k], c[k][:, :1])
    lo, hi = _deadline_range(c, mask, dt, sched["n_bracket"])

    def objective_at(t):
        f, beta = _fixed_deadline(c, mask, t, dt, sched)
        return _objective(c, mask, f, beta, dt)

    phi = dt(_PHI)
    m1, m2 = hi - phi * (hi - lo), lo + phi * (hi - lo)
    c1, c2 = objective_at(m1), objective_at(m2)
    for _ in range(sched["n_golden"]):
        right = c1 > c2
        lo, hi = np.where(right, m1, lo), np.where(right, hi, m2)
        new = np.where(right, lo + phi * (hi - lo), hi - phi * (hi - lo))
        cn = objective_at(new)
        m1, c1, m2, c2 = (np.where(right, m2, new), np.where(right, c2, cn),
                          np.where(right, new, m1), np.where(right, cn, c1))
    f, beta = _fixed_deadline(c, mask, (lo + hi) / dt(2.0), dt, sched)
    beta = np.where(mask, beta, dt(0.0))
    cost = _objective(c, mask, f, beta, dt)
    nonempty = mask.any(axis=1)
    cost = np.where(nonempty, cost + np.asarray(model.cloud[servers], dt),
                    dt(0.0))
    return cost, f, beta, idx, mask


def group_objective(model: Model, groups, f_of, beta_of):
    """Objective (18) in float64 of given per-device (f, beta), no cloud."""
    servers, idx, mask = pack(groups)
    c = model.consts(servers, idx)
    f = np.where(mask, np.asarray(f_of, F64)[idx], 1.0)
    beta = np.asarray(beta_of, F64)[idx]
    return _objective(c, mask, f, beta, F64)


def members_of(assign, active, k):
    out = [[] for _ in range(k)]
    for n in np.flatnonzero(active):
        out[int(assign[n])].append(int(n))
    return [np.asarray(m, np.int64) for m in out]


def unreachable(model: Model, assign) -> int:
    """Active devices placed on a server they cannot reach (17e), or on no
    server at all."""
    assign = np.asarray(assign)
    act = np.flatnonzero(model.active)
    a = assign[act]
    bad = (a < 0) | (a >= model.k)
    ok = ~bad
    bad[ok] = ~model.reach[a[ok], act[ok]]
    return int(bad.sum())


def placement_gaps(model: Model, assign, devices, *, min_residual: int,
                   pick_dtype=None):
    """For each device: how much a transfer to its best reachable server
    would lower the two groups' cost, as a share of their sum (0 where its
    own server is best). A transfer out of a group of ``min_residual`` or
    fewer members is not allowed, as in the paper's Definition 4 with a
    residual group.

    With ``pick_dtype`` the device sits instead where pricing in that
    precision puts it first (the control), and the gap is read in float64.
    Returns (gaps (M,), control gaps (M,) or None)."""
    assign = np.asarray(assign)
    mem = members_of(assign, model.active, model.k)
    size = np.array([m.size for m in mem])
    plans = []
    groups, gid = [], {}

    def gref(key, server, members):
        if key not in gid:
            gid[key] = len(groups)
            groups.append((server, members))
        return gid[key]

    for n in np.asarray(devices, np.int64):
        s = int(assign[n])
        if not model.active[n] or size[s] <= min_residual:
            continue
        cand = [int(k) for k in np.flatnonzero(model.reach[:, n]) if k != s]
        if not cand:
            continue
        base_s = gref(("base", s), s, mem[s])
        minus = gref(("minus", int(n)), s, mem[s][mem[s] != n])
        rows = [(k, gref(("base", k), k, mem[k]),
                 gref(("plus", int(n), k), k, np.append(mem[k], n)))
                for k in cand]
        plans.append((s, base_s, minus, rows))
    if not plans:
        return np.zeros(0), (np.zeros(0) if pick_dtype else None)

    def deltas(cost):
        out = []
        for s, base_s, minus, rows in plans:
            off = cost[minus] - cost[base_s]
            out.append(np.array([0.0] + [off + cost[p] - cost[b]
                                         for _, b, p in rows], F64))
        return out

    cost64 = solve_groups(model, groups, F64)[0].astype(F64)
    d64 = deltas(cost64)
    d_pick = (deltas(solve_groups(model, groups, pick_dtype)[0].astype(F64))
              if pick_dtype is not None else None)
    gaps, ctrl = [], []
    for j, (s, base_s, minus, rows) in enumerate(plans):
        best = int(np.argmin(d64[j]))
        best_cost = cost64[base_s] if best == 0 else cost64[rows[best - 1][1]]
        scale = cost64[base_s] + best_cost
        gaps.append(-d64[j][best] / scale)
        if d_pick is not None:
            chosen = int(np.argmin(d_pick[j]))
            ctrl.append((d64[j][chosen] - d64[j][best]) / scale)
    return np.array(gaps), (np.array(ctrl) if d_pick is not None else None)


def eq17_cost(model: Model, assign, f, beta, dt=F64) -> float:
    """System cost of one global iteration, eqs. (15)-(17), over the active
    devices: energy summed over every edge (cloud terms included), delay
    the slowest edge."""
    act = np.flatnonzero(model.active)
    a = np.asarray(assign)[act]
    cast = lambda x: np.asarray(x, dt)                          # noqa: E731
    fa, ba = cast(np.asarray(f)[act]), cast(np.asarray(beta)[act])
    rate = cast(model.rate_per_hz(a, act)) * ba
    t_com = cast(model.nats[act]) / rate
    e_com = t_com * cast(model.power[act])
    t_cmp = dt(model.local_iters) * cast(model.cycles[act]) / fa
    e_cmp = (dt(model.local_iters) * dt(0.5) * cast(model.alpha[act])
             * fa * fa * cast(model.cycles[act]))
    it = dt(model.edge_iters)
    e_edge = np.zeros(model.k, dt)
    t_edge = np.zeros(model.k, dt)
    np.add.at(e_edge, a, it * (e_com + e_cmp))
    np.maximum.at(t_edge, a, it * (t_com + t_cmp))
    energy = np.sum(e_edge + cast(model.cloud_e))
    delay = np.max(t_edge + cast(model.cloud_t))
    return float(dt(model.lambda_e) * energy + dt(model.lambda_t) * delay)


def allocation_checks(model: Model, assign, f, beta, reported_cost, *,
                      ctrl_dtype=None) -> dict:
    """Numbers of a finalized answer: ``ra_gap`` (worst group's excess of
    its reported (f, beta) over the reference optimum, relative),
    ``ra_infeasible`` (worst excess of a group's bandwidth shares over 1 or
    of an f outside its box, relative) and ``cost_gap`` (the reported eq.-17
    cost against the reference's, relative). With ``ctrl_dtype`` the same
    three numbers of the control: the reference in that precision in the
    program's place."""
    mem = members_of(assign, model.active, model.k)
    groups = [(k, m) for k, m in enumerate(mem) if m.size]
    opt, *_ = solve_groups(model, groups, F64)
    opt = opt - model.cloud[[k for k, _ in groups]]

    def numbers(f_, beta_, cost_):
        got = group_objective(model, groups, f_, beta_)
        ra_gap = float(np.max((got - opt) / opt))
        f_, beta_ = np.asarray(f_, F64), np.asarray(beta_, F64)
        act = model.active
        share = np.array([beta_[m].sum() for _, m in groups])
        box = np.maximum((model.f_min - f_) / model.f_min,
                         (f_ - model.f_max) / model.f_max)[act]
        infeasible = float(max(np.max(share - 1.0), np.max(box),
                               1.0 if (beta_[act] <= 0).any() else 0.0))
        ref = eq17_cost(model, assign, f, beta)
        return {"ra_gap": ra_gap, "ra_infeasible": infeasible,
                "cost_gap": abs(cost_ - ref) / ref}

    out = numbers(f, beta, reported_cost)
    if ctrl_dtype is None:
        return out, None
    _, fc, bc, idx, mask = solve_groups(model, groups, ctrl_dtype)
    f_c = np.array(f, F64, copy=True)
    b_c = np.array(beta, F64, copy=True)
    f_c[idx[mask]] = np.asarray(fc, F64)[mask]
    b_c[idx[mask]] = np.asarray(bc, F64)[mask]
    cost_c = eq17_cost(model, assign, f, beta, ctrl_dtype)
    return out, numbers(f_c, b_c, cost_c)
