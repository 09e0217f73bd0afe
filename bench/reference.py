"""Plain reference of one FL round, and the comparison that decides
``correct``.

Independent of the program: nothing here imports ``repro``. It restates
the round's semantics in straightforward code, from the benchmark's own
weights and data and the seed:

* the model: the reference half of the configuration's model family
  (``bench/families/<family>/reference.py``, which imports nothing of
  the program either), in float32 at the matmul precision the
  configuration states (``precision.matmul``): its weights, its local
  step and its evaluation;
* the seeded draws the round is defined by: client powers and distances
  (numpy ``default_rng(seed)``), Rayleigh fading ``Exp(fold_in(key,
  round))``, minibatch indices ``floor(U * len)`` from
  ``split(fold_in(sample_key, round), N)``, and the random-K draw of the
  baselines from ``fold_in(ctrl_key, round)``;
* the client step: ``local_steps`` SGD steps, the update is the change
  of the parameters, flattened leaf by leaf in sorted-key order;
* decide: FairEnergy's dual ascent (Algorithm 1) in float64, with the
  bandwidth best response found by golden-section search in log-bandwidth
  (not the program's Newton solve), or one of the paper's fixed-K
  baselines: ScoreMax (the K largest update norms at gamma 1) and
  EcoRandom (K at random, at a fixed gamma), each with B_tot / K;
* sparsify: exact top-ceil(gamma*4096) magnitudes per 4096-block, ties to
  the lower index, by a stable sort;
* aggregate and apply: the |D_i|-weighted mean of the selected sparse
  updates, added to the parameters;
* eval: accuracy over the held-out set.

``follow`` replays the rounds of the program's warm-up chunk with the
program's own selection and compression ratios (its decisions are the
"served tokens" of this check: they are discrete, and a near-tie may
legitimately flip between two correct solvers), computing every
continuous quantity itself. ``compare`` turns the two sides into the
numbers that are held against the cell's limits.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

import family

LN2 = math.log(2.0)
BLOCK = 4096
# the program's PRNG stream tags, folded into PRNGKey(seed)
CTRL_STREAM = 1 << 20
SAMPLE_STREAM = 2 << 20


def stated_precision(config: dict):
    """The matmul precision the configuration states it computes at."""
    return jax.lax.Precision[config["precision"]["matmul"].upper()]


# ---------------------------------------------------------------- model ----
def init_params(model: dict, seed: int):
    """The model's initial weights from the seed, by its family's
    ``init_params``."""
    return family.load(model["family"], "reference").init_params(model, seed)


def leaves(p):
    """The parameter leaves in the order the update is flattened in:
    sorted keys at every level of nesting, the order JAX flattens a dict
    in."""
    return jax.tree_util.tree_leaves(p)


def unflatten(vec, like):
    """A flat vector in the nesting and shapes of ``like``."""
    flat, tree = jax.tree_util.tree_flatten(like)
    out, off = [], 0
    for v in flat:
        out.append(vec[off:off + v.size].reshape(v.shape))
        off += v.size
    return jax.tree_util.tree_unflatten(tree, out)


# -------------------------------------------------------------- draws ----
def network(seed: int, n: int, ch: dict):
    """Client powers P [N] and pathloss [N] (float32, as drawn)."""
    rng = np.random.default_rng(seed)
    power = rng.uniform(ch["power_min"], ch["power_max"], n)
    dist = rng.uniform(50.0, ch["cell_radius_m"], n)
    pathloss = 1e-3 * dist ** (-ch["pathloss_exp"])
    return power, np.asarray(pathloss, np.float32)


def gains(seed: int, pathloss, r: int, rayleigh: bool):
    pl = jnp.asarray(pathloss, jnp.float32)
    if not rayleigh:
        return np.asarray(pl)
    fade = jax.random.exponential(
        jax.random.fold_in(jax.random.PRNGKey(seed), r), pl.shape, jnp.float32)
    return np.asarray(pl * fade)


def batch_indices(seed: int, r: int, lengths, steps: int, batch: int):
    """[N, steps, batch] row indices into each client's shard."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), SAMPLE_STREAM)
    ks = jax.random.split(jax.random.fold_in(key, r), len(lengths))
    u = jax.vmap(lambda k: jax.random.uniform(k, (steps, batch)))(ks)
    ln = jnp.asarray(lengths, jnp.int32)[:, None, None]
    return np.asarray(jnp.clip((u * ln).astype(jnp.int32), 0, ln - 1))


def random_k(seed: int, r: int, n: int, k: int):
    key = jax.random.fold_in(jax.random.PRNGKey(seed), CTRL_STREAM)
    u = np.asarray(jax.random.uniform(jax.random.fold_in(key, r), (n,)))
    order = np.argsort(u, kind="stable")
    x = np.zeros(n, bool)
    x[order[:k]] = True
    return x


# ------------------------------------------------------------ physics ----
def comm_energy(gamma, bw, P, h, s_bits, i_bits, n0):
    """E = P (gamma S + I) / (B log2(1 + P h / (N0 B))), float64."""
    bw = np.maximum(bw, 1.0)
    rate = bw * np.log1p(P * h / (n0 * bw)) / LN2
    return P * (gamma * s_bits + i_bits) / rate


def _gss_log_b(phi, lo, hi, iters=90):
    """Elementwise golden-section minimum of a unimodal phi(b) over
    log b in [lo, hi]; returns b."""
    g = (math.sqrt(5.0) - 1.0) / 2.0
    f = lambda v: phi(np.exp(v))
    a, b = lo.copy(), np.full_like(lo, hi)
    c, d = b - g * (b - a), a + g * (b - a)
    fc, fd = f(c), f(d)
    for _ in range(iters):
        left = fc < fd                   # the minimum lies in [a, d]
        a, b = np.where(left, a, c), np.where(left, d, b)
        new = np.where(left, b - g * (b - a), a + g * (b - a))
        f_new = f(new)
        c, d, fc, fd = (np.where(left, new, d), np.where(left, c, new),
                        np.where(left, f_new, fd), np.where(left, fc, f_new))
    return np.exp(0.5 * (a + b))


class FairEnergyRef:
    """FairEnergy's per-round decide (Algorithm 1), float64."""

    def __init__(self, n, fe: dict, ch: dict, s_bits, i_bits, power):
        self.fe, self.ch = fe, ch
        self.s_bits, self.i_bits = s_bits, i_bits
        self.P = np.asarray(power, np.float64)
        self.lam, self.mu = 0.0, np.zeros(n)
        self.q = np.full(n, fe["q0"])
        self.eta = None

    def calibrate(self, u_norms, h):
        """eta := eta_rel * median E(0.5, B_tot/N) / median(0.5 ||u||)."""
        n = len(u_norms)
        e = comm_energy(0.5, self.ch["bandwidth_total"] / n, self.P,
                        np.asarray(h, np.float64), self.s_bits, self.i_bits,
                        self.ch["noise_density"])
        self.eta = (self.fe["eta_rel"] * float(np.median(e))
                    / max(float(np.median(0.5 * np.asarray(u_norms))), 1e-12))

    def _best_response(self, lam, u, h):
        fe, ch = self.fe, self.ch
        grid = np.asarray(fe["gamma_grid"], np.float64)[None, :]
        P, hh = self.P[:, None], h[:, None]
        b_tot = ch["bandwidth_total"]

        def energy(b):
            return comm_energy(grid, b * b_tot, P, hh, self.s_bits,
                               self.i_bits, ch["noise_density"])
        b = _gss_log_b(lambda b: energy(b) + lam * b,
                       np.full((len(u), grid.shape[1]),
                               math.log(fe["b_min_frac"])), 0.0)
        phi = energy(b) + lam * b - self.eta * u[:, None] * grid
        g = np.argmin(phi, axis=1)
        take = lambda t: np.take_along_axis(t, g[:, None], 1)[:, 0]
        return take(np.broadcast_to(grid, b.shape)), take(b), take(energy(b))

    def decide(self, u_norms, h):
        fe = self.fe
        u = np.asarray(u_norms, np.float64)
        h = np.asarray(h, np.float64)
        rho, pi_min = fe["rho"], fe["pi_min"]
        lam, mu = self.lam, self.mu.copy()
        for _ in range(fe["inner_iters"]):
            gam, b, e = self._best_response(lam, u, h)
            x = (e + lam * b < self.eta * u * gam + mu * (1 - rho))
            new_lam = max(lam + fe["alpha_lambda"] * (np.sum(x * b) - 1.0),
                          0.0)
            new_mu = np.maximum(mu + fe["alpha_mu"] * (
                pi_min - rho * self.q - (1 - rho) * x), 0.0)
            res = max(abs(new_lam - lam) / fe["alpha_lambda"],
                      float(np.max(np.abs(new_mu - mu))) / fe["alpha_mu"])
            lam, mu = new_lam, new_mu
            if res <= fe["dual_tol"]:
                break
        gam, b, e = self._best_response(lam, u, h)
        benefit = self.eta * u * gam + mu * (1 - rho) - e - lam * b
        x = benefit > 0
        # greedy repair: keep clients (fairness-deficit ones first, then
        # by benefit) while the bandwidth budget holds
        prio = np.where(pi_min - rho * self.q > 0, 1e6, 0.0) + benefit
        order = np.argsort(np.where(x, -prio, np.inf), kind="stable")
        keep = np.zeros_like(x)
        keep[order] = (np.cumsum(b[order] * x[order]) <= 1.0) & x[order]
        x = x & keep
        self.lam, self.mu = lam, mu
        return dict(x=x, gamma=np.where(x, gam, 0.0),
                    bandwidth=np.where(x, b * self.ch["bandwidth_total"], 0.0),
                    energy=np.where(x, e, 0.0))

    def observe(self, x):
        """The participation EMA q after a round with selection x."""
        self.q = self.fe["rho"] * self.q + (1 - self.fe["rho"]) * x


# ---------------------------------------------------------- sparsify ----
@jax.jit
def block_topk(rows, ks):
    """rows [M, BLOCK], ks [M]: keep the ks largest |x| per row, ties to
    the lower index (stable sort)."""
    order = jnp.argsort(-jnp.abs(rows), axis=1, stable=True)
    rank = jnp.argsort(order, axis=1)
    return jnp.where(rank < ks[:, None], rows, 0.0)


def sparsify(update, gamma):
    """One client's flat update, top-ceil(gamma*BLOCK) per block."""
    d = update.shape[0]
    nb = -(-d // BLOCK)
    k = int(min(max(math.ceil(float(np.float32(gamma)) * BLOCK), 1), BLOCK))
    if k >= BLOCK:
        return update
    rows = jnp.pad(update, (0, nb * BLOCK - d)).reshape(nb, BLOCK)
    return block_topk(rows, jnp.full((nb,), k, jnp.int32)).reshape(-1)[:d]


def fixed_k_decide(traffic, seed, r, norms, h, power, ch, s_bits, i_bits):
    """The paper's fixed-K baselines, each giving B_tot / K to every
    selected client: ScoreMax (the K largest update norms, ties to the
    lower index, gamma 1) and EcoRandom (K at random, gamma
    ``eco_gamma``)."""
    n, k, ctrl = len(norms), traffic["fixed_k"], traffic["controller"]
    if ctrl == "scoremax":
        x = np.zeros(n, bool)
        x[np.argsort(-np.asarray(norms), kind="stable")[:k]] = True
    else:
        x = random_k(seed, r, n, k)
    gamma = float(np.float32(traffic["eco_gamma"])) if ctrl == "ecorandom" \
        else 1.0
    bw = ch["bandwidth_total"] / k
    e = comm_energy(gamma, bw, power, h, s_bits, i_bits, ch["noise_density"])
    return dict(x=x, gamma=x * gamma, bandwidth=x * bw,
                energy=np.where(x, e, 0.0))


# ------------------------------------------------------------ rounds ----
def follow(config: dict, traffic: dict, data: dict, params0, rounds: int,
           logs=None, dtype=jnp.float32, precision=None):
    """Runs ``rounds`` FL rounds from ``params0``.

    With ``logs`` (the program's per-round x and gamma) the reference
    aggregates the program's selection at the program's compression
    ratios, and decides on its own only to compare. Without, it uses its
    own decisions: the reference put in the program's place, as the
    lower-precision control runs it. ``precision`` defaults to the one the
    configuration states. Returns per-round losses,
    accuracies (where ``logs`` has one, else at round 0 and the last),
    its own decisions, the final parameters as float32 numpy leaves, and
    the energy each logged decision costs by the reference's physics.
    The fleet's draws (powers, distances, fading, minibatches, random-K)
    come from the configuration's ``fleet_seed``, as the program draws
    them from the seed it is given."""
    precision = precision or stated_precision(config)
    seed = config["fleet_seed"]
    ch, fe = config["channel"], traffic.get("fairenergy", {})
    n = config["n_clients"]
    steps, batch = traffic["local_steps"], traffic["local_batch"]
    parts = data["parts"]
    lengths = np.array([len(p) for p in parts])
    weights = lengths / lengths.sum()
    d = sum(int(np.prod(v.shape)) for v in leaves(params0))
    s_bits, i_bits = 32.0 * d, float(d)
    power, pathloss = network(seed, n, ch)
    model = family.load(config["model"]["family"], "reference")
    client_step = model.client_step(traffic["lr"], dtype, precision)
    accuracy = model.accuracy(data, dtype, precision)
    params = jax.tree_util.tree_map(lambda v: jnp.asarray(v, dtype), params0)
    offsets = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    flat_index = np.concatenate(parts)
    fair = traffic["controller"] == "fairenergy"
    ctrl = FairEnergyRef(n, fe, ch, s_bits, i_bits, power) if fair else None
    out = dict(loss=[], acc=[], own=[], e_logged=[])
    for r in range(rounds):
        h = gains(seed, pathloss, r, ch["rayleigh"])
        local = batch_indices(seed, r, lengths, steps, batch)
        rows = flat_index[offsets[:, None, None] + local]
        upd, losses = client_step(params, model.minibatch(data, rows))
        norms = np.asarray(jnp.sqrt(jnp.sum(jnp.square(
            upd.astype(jnp.float32)), axis=1)), np.float64)
        if fair:
            if r == 0:
                ctrl.calibrate(norms, h)
            own = ctrl.decide(norms, h)
        else:
            own = fixed_k_decide(traffic, seed, r, norms, h, power, ch,
                                 s_bits, i_bits)
        x, gamma = ((np.asarray(logs[r]["x"], bool),
                     np.asarray(logs[r]["gamma"], np.float64))
                    if logs is not None else (own["x"], own["gamma"]))
        if logs is not None:
            bw = np.asarray(logs[r]["bandwidth"], np.float64)
            out["e_logged"].append(np.where(x, comm_energy(
                gamma, np.where(x, bw, 1.0), power, h, s_bits, i_bits,
                ch["noise_density"]), 0.0))
        if fair:
            ctrl.observe(x)
        sel = np.flatnonzero(x)
        w = weights[sel] / weights[sel].sum()
        agg = jnp.zeros((d,), jnp.float32)
        for i, wi in zip(sel, w):
            g = min(max(float(gamma[i]), 1e-6), 1.0)
            agg = agg + jnp.float32(wi) * sparsify(
                upd[i].astype(jnp.float32), g)
        params = jax.tree_util.tree_map(
            lambda p, a: (p + a.astype(dtype)).astype(dtype),
            params, unflatten(agg, params))
        out["loss"].append(float(jnp.mean(losses.astype(jnp.float32))))
        evaluated = (not math.isnan(logs[r]["accuracy"]) if logs is not None
                     else r in (0, rounds - 1))
        out["acc"].append(float(accuracy(params))
                          if evaluated else float("nan"))
        out["own"].append(own)
    out["params"] = jax.tree_util.tree_map(
        lambda v: np.asarray(v, np.float32), params)
    return out


def as_logs(out):
    """A free-running reference's rounds in the form ``follow`` reads."""
    return [dict(x=o["x"], gamma=o["gamma"], bandwidth=o["bandwidth"],
                 energy=o["energy"], loss=l, accuracy=a)
            for o, l, a in zip(out["own"], out["loss"], out["acc"])]


# ---------------------------------------------------------- compare ----
LOSS_ROUNDS = 3
EDGE = 1e-3       # a bandwidth within this (relative) of a bound of
                  # [b_min, 1] sits on it and implies no price


def implied_prices(gamma, bw, P, h, ch, s_bits, i_bits, b_lo):
    """The bandwidth price lam_i at which each client's (gamma_i, B_i)
    is its best response: -dE/db at b_i = B_i / B_tot, NaN on a bound.
    FairEnergy gives every selected client its best response to one
    common price, so these agree to the solver's accuracy."""
    b_tot, n0 = ch["bandwidth_total"], ch["noise_density"]
    c = P * h / n0
    snr = c / bw
    rate = bw * np.log1p(snr) / LN2
    d_rate = np.log1p(snr) / LN2 - snr / ((1.0 + snr) * LN2)
    lam = b_tot * P * (gamma * s_bits + i_bits) * d_rate / rate ** 2
    b = bw / b_tot
    return np.where((b > b_lo * (1 + EDGE)) & (b < 1 - EDGE), lam, np.nan)


def bandwidth_gaps(logs, config, traffic, params0):
    """Per round, the widest relative gap between a selected client's
    logged bandwidth and the reference's best response at the round's
    price. FairEnergy: the price is the median of the prices the logged
    allocations imply; the fixed-K baselines split B_tot evenly."""
    ch, fe = config["channel"], traffic.get("fairenergy", {})
    n, seed = config["n_clients"], config["fleet_seed"]
    d = sum(int(np.prod(v.shape)) for v in leaves(params0))
    s_bits, i_bits = 32.0 * d, float(d)
    power, pathloss = network(seed, n, ch)
    b_tot = ch["bandwidth_total"]
    gaps = []
    for r, lg in enumerate(logs):
        x = np.asarray(lg["x"], bool)
        bw = np.asarray(lg["bandwidth"], np.float64)[x]
        gam = np.asarray(lg["gamma"], np.float64)[x]
        if not x.any():
            gaps.append(0.0)
            continue
        if traffic["controller"] != "fairenergy":
            want = np.full_like(bw, b_tot / traffic["fixed_k"])
        else:
            h = gains(seed, pathloss, r, ch["rayleigh"])[x].astype(np.float64)
            P = power[x]
            lam = implied_prices(gam, bw, P, h, ch, s_bits, i_bits,
                                 fe["b_min_frac"])
            lam = float(np.nanmedian(lam)) if np.isfinite(lam).any() else 0.0
            energy = lambda b: comm_energy(gam, b * b_tot, P, h, s_bits,
                                           i_bits, ch["noise_density"])
            want = b_tot * _gss_log_b(lambda b: energy(b) + lam * b,
                                      np.full(bw.shape,
                                              math.log(fe["b_min_frac"])),
                                      0.0)
        gaps.append(float(np.max(np.abs(bw - want) / want)))
    return gaps


def compare(logs, prog_params, ref, params0, config, traffic):
    """The numbers held against the cell's limits, and per-round detail.

    * ``loss0_gap``: relative gap of round 0's mean client loss (same
      weights, same minibatches: the steadiest number);
    * ``loss_gap``: the widest such gap over the first ``LOSS_ROUNDS``
      rounds (later rounds carry the round-off of every earlier step,
      amplified by training);
    * ``loss_last_gap``: the gap of the last round, where a precision
      that loses small updates has slowed training most;
    * ``param_gap``: worst leaf of | ||dp_prog|| - ||dp_ref|| | over
      max(||dp_ref||, median leaf's), dp the change over the rounds;
      leaves the reference moves by under a thousandth of the median
      leaf's are left out;
    * ``energy_gap``: widest relative gap between a selected client's
      logged energy and the reference's energy for the logged gamma and
      bandwidth on the round's channel;
    * ``bandwidth_gap``: widest relative gap between a logged bandwidth
      and the reference's best response at the round's price
      (``bandwidth_gaps``);
    * ``select_mismatch``: share of client-rounds where the reference's
      own selection or compression ratio, decided on its own update
      norms, differs from the program's;
    * ``acc_gap``: widest gap of a logged test accuracy."""
    lp = np.array([l["loss"] for l in logs])
    lr = np.array(ref["loss"])
    loss_rel = np.abs(lp - lr) / np.abs(lr)
    nums = {"loss0_gap": float(loss_rel[0]),
            "loss_gap": float(np.max(loss_rel[:LOSS_ROUNDS])),
            "loss_last_gap": float(loss_rel[-1])}
    p0 = leaves(params0)
    dp = [np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b))
          for a, b in zip(leaves(prog_params), p0)]
    dr = [np.linalg.norm(np.asarray(a, np.float64) - np.asarray(b))
          for a, b in zip(leaves(ref["params"]), p0)]
    med = float(np.median(dr))
    leaf_gap = [abs(a - b) / max(b, med) for a, b in zip(dp, dr)
                if b >= 1e-3 * med]
    nums["param_gap"] = float(max(leaf_gap))
    e_gap, miss = [], []
    for lg, own, e_ref in zip(logs, ref["own"], ref["e_logged"]):
        x = np.asarray(lg["x"], bool)
        e = np.asarray(lg["energy"], np.float64)
        e_gap.append(float(np.max(np.abs(e[x] - e_ref[x]) / e_ref[x],
                                  initial=0.0)))
        g = np.asarray(lg["gamma"], np.float64)
        both = x & own["x"]
        bad = (x != own["x"]) | (both & (np.abs(g - own["gamma"]) > 1e-6))
        miss.append(int(bad.sum()))
    bw_gap = bandwidth_gaps(logs, config, traffic, params0)
    nums["energy_gap"] = max(e_gap)
    nums["bandwidth_gap"] = max(bw_gap)
    nums["select_mismatch"] = sum(miss) / (len(logs) * len(logs[0]["x"]))
    acc = [abs(l["accuracy"] - a) for l, a in zip(logs, ref["acc"])
           if not math.isnan(l["accuracy"])]
    nums["acc_gap"] = float(max(acc))
    detail = dict(loss_rel=loss_rel.tolist(), energy_gap=e_gap,
                  bandwidth_gap=bw_gap, mismatched=miss,
                  selected=[int(np.sum(l["x"])) for l in logs],
                  leaf_gap=leaf_gap)
    return nums, all(np.isfinite(v) for v in nums.values()), detail


def judge(nums: dict, ok: bool, limits: dict):
    """``correct``, and each number beside its limit. Only the numbers
    the cell's limits file names are held to a limit."""
    shown = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    return ok and all(nums[k] <= limits[k] for k in limits), shown
