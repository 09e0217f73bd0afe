"""Paper experiment reproduction (Figs. 1-3, Table I).

Setting mirrors Sec. VII: N clients, ~2M-param CNN, non-IID Dirichlet
(beta=0.3) FMNIST-like data, B_tot=10 MHz, P_i ~ U[0.1,0.3] mW,
gamma in [0.1,1], pi_min=0.2, rho=0.6, lr=0.01 (we use 0.05 + 2 local
steps for CPU-budget convergence; the paper's 0.01/1-step setting is a
flag). Baseline K = mean FairEnergy selection count; EcoRandom uses the
min gamma / min bandwidth observed for FairEnergy (paper protocol).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ChannelConfig, FairEnergyConfig, FLConfig
from repro.configs.fmnist_cnn import CONFIG as CNN_FULL
from repro.data import ClientDataset, dirichlet_partition, make_fmnist_like
from repro.fl import FederatedTrainer
from repro.models import cnn
from repro.scenarios import available_scenarios, get_scenario

DATA_KW = dict(confusion=0.55, label_noise=0.05, noise=0.9)


def build(n_clients=20, rounds=60, n_train=12000, n_test=2000, seed=0,
          lr=0.05, local_steps=2, mesh=None, scenario=None,
          deadline=None, staleness_a=None, fault_rate=None, crash_rate=None,
          churn=None, defense=None, clusters=None, pool_frac=None,
          mobility_sigma=None, max_retx=None, burst_p=None,
          price_outage=None, bits_grid=None, pallas=None):
    """Trainer factory for the paper's setting. ``pallas=True`` routes
    the solver and the top-k compression through the Pallas kernels
    (``use_pallas_solver`` / ``use_pallas_compression``), ``False``
    through the jnp paths; ``None`` (default) keeps the jnp solver and
    picks the top-k by backend (the kernel on a TPU). Returns
    ``(make, fl_cfg)``; ``make(controller, **kw)`` builds a
    ``FederatedTrainer``."""
    cfg = CNN_FULL
    scn = get_scenario(scenario) if isinstance(scenario, str) else scenario
    beta = scn.beta(0.3) if scn else 0.3
    ch_cfg = ChannelConfig(n_clients=n_clients)
    fe_cfg = FairEnergyConfig()
    profile = None
    async_cfg = None
    fault_cfg = None
    defense_cfg = None
    mobility_cfg = None
    hierarchy_cfg = None
    link_cfg = None
    if clusters is not None or pool_frac is not None:
        from repro.core.hierarchy import HierarchyConfig
        hierarchy_cfg = HierarchyConfig(
            clusters=clusters if clusters is not None else 1,
            pool_frac=pool_frac if pool_frac is not None else 1.0)
    if scn:
        ch_cfg = scn.apply_channel(ch_cfg)
        fe_cfg = scn.apply_fe(fe_cfg)
        profile = scn.device_profile(n_clients, seed=seed)
        async_cfg = scn.async_config(deadline_s=deadline,
                                     staleness_a=staleness_a)
        fault_cfg = scn.fault_config(crash_rate=crash_rate,
                                     corrupt_rate=fault_rate)
        defense_cfg = scn.defense_config(defended=defense)
        mobility_cfg = scn.mobility_config(sigma_db=mobility_sigma)
        link_cfg = scn.link_config(max_retx=max_retx, burst_p=burst_p,
                                   price_outage=price_outage)
    elif mobility_sigma is not None and mobility_sigma > 0.0:
        from repro.core.channel import MobilityConfig
        mobility_cfg = MobilityConfig(sigma_db=mobility_sigma)
    if scn is None and deadline is not None:
        from repro.core.rounds import AsyncConfig
        async_cfg = AsyncConfig(deadline_s=deadline,
                                staleness_a=staleness_a
                                if staleness_a is not None else 0.5)
    if scn is None and (fault_rate or crash_rate or churn):
        from repro.core.faults import FaultConfig
        fault_cfg = FaultConfig(
            crash_rate=crash_rate or 0.0, corrupt_rate=fault_rate or 0.0,
            churn_dwell=4 if churn else 0, churn_away=churn or 0.3)
        fault_cfg = fault_cfg if fault_cfg.enabled else None
    if scn is None and defense:
        from repro.core.faults import DefenseConfig
        defense_cfg = DefenseConfig()
    if scn is None and (burst_p or price_outage or max_retx is not None):
        from repro.core.link import LinkConfig
        link_cfg = LinkConfig(
            outage=True, max_retx=max_retx if max_retx is not None else 2,
            burst_p=burst_p or 0.0, i_burst_n0=99.0 if burst_p else 0.0,
            price_outage=bool(price_outage))
        link_cfg = link_cfg if link_cfg.enabled else None
    if bits_grid is not None:
        # explicit CLI grid wins over the scenario preset: the solver's
        # decision grid becomes the joint (gamma, bits) cross product and
        # the engine quantizes payloads at the decided width
        fe_cfg = dataclasses.replace(
            fe_cfg, bits_grid=tuple(float(b) for b in bits_grid))
    if pallas:
        fe_cfg = dataclasses.replace(fe_cfg, use_pallas_solver=True)
    imgs, labels = make_fmnist_like(n_train, seed=seed, **DATA_KW)
    ti, tl = make_fmnist_like(n_test, seed=seed + 999,
                              **dict(DATA_KW, label_noise=0.0))
    parts = dirichlet_partition(labels, n_clients, beta, seed=seed)
    fl_cfg = FLConfig(rounds=rounds, local_batch=64, local_steps=local_steps,
                      lr=lr, dirichlet_beta=beta)
    datasets = [ClientDataset(imgs[p], labels[p], fl_cfg.local_batch, seed=i)
                for i, p in enumerate(parts)]
    params = cnn.init_cnn(jax.random.PRNGKey(seed), cfg)
    loss_fn = lambda p, b: cnn.cnn_loss(p, b, cfg)
    ti_j, tl_j = jnp.asarray(ti), jnp.asarray(tl)

    @jax.jit
    def eval_fn(p):
        lg = cnn.cnn_forward(p, ti_j, cfg)
        return jnp.mean((jnp.argmax(lg, -1) == tl_j).astype(jnp.float32))

    def make(controller, **kw):
        return FederatedTrainer(model_loss=loss_fn, model_params=params,
                                client_datasets=datasets, eval_fn=eval_fn,
                                fl_cfg=fl_cfg, fe_cfg=fe_cfg,
                                ch_cfg=ch_cfg, controller=controller,
                                seed=seed, mesh=mesh, device_profile=profile,
                                async_cfg=async_cfg, fault_cfg=fault_cfg,
                                defense=defense_cfg, link_cfg=link_cfg,
                                hierarchy=hierarchy_cfg,
                                mobility=mobility_cfg,
                                use_pallas_compression=pallas, **kw)
    return make, fl_cfg


def run_all(n_clients=20, rounds=60, target=0.80, seed=0, verbose=True,
            extra_baselines=False, eval_every=1, sweep_seeds=None,
            config_sweep=None, **build_kw):
    """Runs FairEnergy first (to fix K / eco params per paper protocol),
    then the baselines — each through the fused ``run_scanned`` engine
    (``eval_every`` strides the in-scan accuracy evaluation). With
    ``sweep_seeds``, each strategy additionally runs a vmapped multi-seed
    sweep (``run_sweep``) for mean±std error bars at roughly single-run
    wall-clock. ``config_sweep`` (a dict of FEParams overrides, e.g.
    ``{"eta": [...], "rho": [...], "b_tot": [...]}`` — lists are crossed
    into lanes by the CLI) additionally runs FairEnergy once per
    hyper-parameter lane x seed, all inside ONE jitted program (the
    config scalars are traced operands of the solver, so lanes share a
    single trace). Returns the results dict."""
    make, fl_cfg = build(n_clients=n_clients, rounds=rounds, seed=seed, **build_kw)

    t0 = time.time()
    fe = make("fairenergy")
    fe.run_scanned(rounds, eval_every=eval_every, verbose=verbose)
    k = max(1, int(round(np.mean([lg.n_selected for lg in fe.history]))))
    eco_gamma = float(min((g for lg in fe.history for g in lg.gamma[lg.selected]),
                          default=0.1))
    # EcoRandom's "bandwidth observed in FairEnergy": the literal minimum is
    # degenerate with a continuous GSS bracket (marginal clients get ~0 Hz,
    # i.e. unbounded transmit time), so we use the MEDIAN allocation —
    # preserving the paper's intent of a communication-cost floor
    bws = [b for lg in fe.history for b in lg.bandwidth[lg.selected] if b > 0]
    eco_bw = float(np.median(bws)) if bws else fe.ch_cfg.bandwidth_total / max(k, 1)

    runs = {"fairenergy": fe}
    strategies = ["scoremax", "ecorandom"] + (
        ["randomfull", "channelgreedy"] if extra_baselines else [])
    base_kw = dict(fixed_k=k, eco_gamma=eco_gamma, eco_bandwidth=eco_bw)
    for s in strategies:
        tr = make(s, **base_kw)
        tr.run_scanned(rounds, eval_every=eval_every, verbose=verbose)
        runs[s] = tr

    scn = build_kw.get("scenario")
    results = {"k": k, "eco_gamma": eco_gamma, "eco_bandwidth": eco_bw,
               "rounds": rounds, "n_clients": n_clients,
               "scenario": (scn if isinstance(scn, str) or scn is None
                            else scn.name),
               "elapsed_s": round(time.time() - t0, 1), "strategies": {}}
    for name, tr in runs.items():
        part = tr.participation_counts()
        results["strategies"][name] = {
            "accuracy": tr.accuracy_curve().tolist(),
            "energy_per_round_J": tr.energy_per_round().tolist(),
            "energy_to_target_J": tr.energy_to_accuracy(target),
            "participation": {"min": int(part.min()), "max": int(part.max()),
                              "std": float(part.std())},
            "mean_selected": float(np.mean([lg.n_selected for lg in tr.history])),
            "mean_gamma": tr.mean_gamma_selected(),
        }
        if tr.history and tr.history[0].t_round is not None:
            results["strategies"][name].update(
                simulated_time_s=tr.simulated_time(),
                wallclock_to_target_s=tr.wallclock_to_accuracy(target),
                n_late=int(sum(lg.n_late for lg in tr.history)),
                n_stale=int(sum(lg.n_stale for lg in tr.history)))
        if tr.history and tr.history[0].n_faulted is not None:
            results["strategies"][name].update(
                n_faulted=int(sum(lg.n_faulted for lg in tr.history)),
                n_rejected=int(sum(lg.n_rejected for lg in tr.history)),
                mean_clip_frac=float(np.mean([lg.clip_frac
                                              for lg in tr.history])),
                n_fallback_rounds=int(sum(bool(lg.fallback)
                                          for lg in tr.history)))
        if tr.history and tr.history[0].n_retx is not None:
            results["strategies"][name].update(
                n_retx=int(sum(lg.n_retx for lg in tr.history)),
                n_outage=int(sum(lg.n_outage for lg in tr.history)),
                mean_goodput_frac=float(np.mean([lg.goodput_frac
                                                 for lg in tr.history])),
                e_retx_J=float(sum(lg.e_retx for lg in tr.history)))
        if tr.history and tr.history[0].bits is not None:
            sel_bits = [b for lg in tr.history
                        for b in lg.bits[lg.selected]]
            results["strategies"][name].update(
                mean_bits=float(np.mean(sel_bits)) if sel_bits else 32.0,
                e_saved_J=float(sum(lg.e_saved for lg in tr.history)))

    if sweep_seeds:
        sweep = {"seeds": [int(s) for s in sweep_seeds], "strategies": {}}
        for name in runs:
            kw = {} if name == "fairenergy" else base_kw
            outs = make(name, **kw).run_sweep(sweep_seeds, rounds,
                                              eval_every=eval_every)
            acc, energy = outs["accuracy"], outs["energy"].sum(-1)
            with warnings.catch_warnings():
                # eval_every-skipped rounds are NaN in every lane — the
                # all-NaN mean/std is the intended output, not a problem
                warnings.simplefilter("ignore", RuntimeWarning)
                acc_mean = np.nanmean(acc, axis=0).tolist()
                acc_std = np.nanstd(acc, axis=0).tolist()
            sweep["strategies"][name] = {
                "final_acc_mean": float(np.nanmean(acc[:, -1])),
                "final_acc_std": float(np.nanstd(acc[:, -1])),
                "acc_mean": acc_mean,
                "acc_std": acc_std,
                "energy_per_round_mean_J": float(energy.mean()),
                "energy_per_round_std_J": float(energy.mean(1).std()),
            }
        results["sweep"] = sweep
        results["elapsed_s"] = round(time.time() - t0, 1)

    if config_sweep:
        seeds = sweep_seeds or [seed]
        outs = make("fairenergy").run_sweep(seeds, rounds,
                                            eval_every=eval_every,
                                            configs=config_sweep)
        acc, energy = outs["accuracy"], outs["energy"].sum(-1)  # [C,S,R]
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            lanes = []
            for c in range(acc.shape[0]):
                lanes.append({
                    "config": {k: v[c] for k, v in outs["configs"].items()},
                    "final_acc_mean": float(np.nanmean(acc[c, :, -1])),
                    "final_acc_std": float(np.nanstd(acc[c, :, -1])),
                    "energy_per_round_mean_J": float(energy[c].mean()),
                    "mean_selected": float(outs["x"][c].sum(-1).mean()),
                })
        results["config_sweep"] = {"seeds": [int(s) for s in seeds],
                                   "lanes": lanes}
        results["elapsed_s"] = round(time.time() - t0, 1)
    return results


def _json_safe(obj):
    """NaN -> null (eval_every-skipped rounds): bare NaN tokens are not
    valid JSON and break strict parsers (jq, JSON.parse)."""
    if isinstance(obj, float) and np.isnan(obj):
        return None
    if isinstance(obj, dict):
        return {k: _json_safe(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_json_safe(v) for v in obj]
    return obj


def main(out="experiments/fl_results.json", **kw):
    res = run_all(**kw)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(_json_safe(res), f, indent=1)
    summarize(res)
    return res


def summarize(res):
    scn = res.get("scenario")
    print(f"\n=== FL results (N={res['n_clients']}, {res['rounds']} rounds, "
          f"K={res['k']}{', scenario=' + scn if scn else ''}) ===")
    print(f"{'strategy':14s}{'final_acc':>10s}{'E/round mJ':>12s}"
          f"{'E->80% J':>12s}{'part min/max/std':>20s}")
    for name, s in res["strategies"].items():
        acc = s["accuracy"][-1]
        epr = np.mean(s["energy_per_round_J"]) * 1e3
        e2t = s["energy_to_target_J"]
        p = s["participation"]
        print(f"{name:14s}{acc:10.3f}{epr:12.3f}"
              f"{(f'{e2t:.3f}' if e2t else 'n/a'):>12s}"
              f"{p['min']:>8d}/{p['max']:<4d}{p['std']:6.2f}")
        if "n_faulted" in s:
            print(f"{'':14s}faults: {s['n_faulted']} injected, "
                  f"{s['n_rejected']} rejected, clip "
                  f"{s['mean_clip_frac']:.2f}, "
                  f"{s['n_fallback_rounds']} solver-fallback rounds")
        if "n_retx" in s:
            print(f"{'':14s}link: {s['n_retx']} retx, {s['n_outage']} "
                  f"outages, goodput {s['mean_goodput_frac']:.2f}, "
                  f"retx energy {s['e_retx_J']*1e3:.3f} mJ")
        if "mean_bits" in s:
            print(f"{'':14s}quantized: mean width "
                  f"{s['mean_bits']:.1f} bits, "
                  f"{s['e_saved_J']*1e3:.3f} mJ saved vs 32-bit payloads")
    fe = res["strategies"]["fairenergy"].get("energy_to_target_J")
    for base in ("scoremax", "ecorandom"):
        bt = res["strategies"].get(base, {}).get("energy_to_target_J")
        if fe and bt:
            print(f"FairEnergy uses {100 * (1 - fe / bt):.0f}% less energy than "
                  f"{base} to reach target (paper: 71% vs ScoreMax, 79% vs EcoRandom)")
    if "sweep" in res:
        sw = res["sweep"]
        print(f"\n--- {len(sw['seeds'])}-seed sweep (vmapped scan engine) ---")
        for name, s in sw["strategies"].items():
            print(f"{name:14s} final acc {s['final_acc_mean']:.3f} "
                  f"± {s['final_acc_std']:.3f}   E/round "
                  f"{s['energy_per_round_mean_J']*1e3:.3f} "
                  f"± {s['energy_per_round_std_J']*1e3:.3f} mJ")
    if "config_sweep" in res:
        cs = res["config_sweep"]
        print(f"\n--- fairenergy config sweep ({len(cs['lanes'])} lanes x "
              f"{len(cs['seeds'])} seeds, one jitted program) ---")
        for ln in cs["lanes"]:
            knobs = " ".join(f"{k}={v:.3g}" for k, v in ln["config"].items())
            print(f"{knobs:40s} acc {ln['final_acc_mean']:.3f} "
                  f"± {ln['final_acc_std']:.3f}  E/round "
                  f"{ln['energy_per_round_mean_J']*1e3:.3f} mJ  "
                  f"sel {ln['mean_selected']:.1f}")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--clients", "--n-clients", dest="clients", type=int,
                    default=20, help="number of FL clients N")
    ap.add_argument("--rounds", type=int, default=60)
    ap.add_argument("--paper", action="store_true",
                    help="full paper scale: N=50, 150 rounds")
    ap.add_argument("--extra-baselines", action="store_true")
    ap.add_argument("--seeds", type=int, default=0,
                    help="N>0: vmapped N-seed sweep per strategy (error bars)")
    ap.add_argument("--eval-every", type=int, default=1,
                    help="accuracy-eval stride inside the scanned engine")
    ap.add_argument("--sweep-eta", default=None,
                    help="comma-separated eta values: fairenergy config "
                         "sweep lanes (crossed with --sweep-rho/--sweep-btot; "
                         "all lanes x seeds run as one jitted program)")
    ap.add_argument("--sweep-rho", default=None,
                    help="comma-separated rho values (see --sweep-eta)")
    ap.add_argument("--sweep-btot", default=None,
                    help="comma-separated B_tot values in Hz (see --sweep-eta)")
    ap.add_argument("--scenario", default=None,
                    choices=available_scenarios(),
                    help="named scenario preset (repro.scenarios): device "
                         "fleet + batteries + data skew + channel + async-"
                         "round knobs")
    ap.add_argument("--deadline", type=float, default=None,
                    help="round deadline T_round in seconds "
                         "(repro.core.rounds): selected clients past it are "
                         "dropped from the aggregate; overrides the "
                         "scenario's preset deadline")
    ap.add_argument("--staleness-a", type=float, default=None,
                    help="staleness decay exponent a in w(tau)=(1+tau)^-a "
                         "(only takes effect when the scenario buffers late "
                         "updates, e.g. --scenario straggler)")
    ap.add_argument("--fault-rate", type=float, default=None,
                    help="payload corruption rate (repro.core.faults): "
                         "fraction of delivered updates replaced with "
                         "NaN/Inf/scaled garbage; overrides the scenario "
                         "preset's corrupt_rate")
    ap.add_argument("--crash-rate", type=float, default=None,
                    help="mid-round crash rate: selected clients that pay "
                         "partial energy but deliver no update; overrides "
                         "the scenario preset's crash_rate")
    ap.add_argument("--churn", type=float, default=None,
                    help="open-population away probability on 4-round dwell "
                         "epochs (scenario-less runs; use --scenario churn "
                         "for the preset)")
    ap.add_argument("--defense", action="store_true", default=None,
                    help="robust aggregation (finite screen + norm clipping "
                         "to a streaming quantile); overrides the scenario "
                         "preset's defended flag")
    ap.add_argument("--clusters", type=int, default=None,
                    help="hierarchical control (repro.core.hierarchy): "
                         "k-means client clusters for stratified candidate "
                         "sampling; 1 (default) keeps full-population "
                         "control")
    ap.add_argument("--pool-frac", type=float, default=None,
                    help="per-round candidate pool fraction sampled prop. "
                         "to fairness deficit; controllers solve on the "
                         "pooled slice only (1.0 = full population)")
    ap.add_argument("--max-retx", type=int, default=None,
                    help="HARQ retransmission budget per round "
                         "(repro.core.link): extra attempts charge real "
                         "airtime energy; overrides the scenario preset "
                         "(scenario-less runs get outage with a 6 dB "
                         "fade margin)")
    ap.add_argument("--burst-p", type=float, default=None,
                    help="Gilbert-Elliott quiet->burst probability per "
                         "round: bursty interference raising the noise "
                         "floor; overrides the scenario preset's burst_p")
    ap.add_argument("--price-outage", action="store_true", default=None,
                    help="fold the expected attempt count 1/(1-p_out) into "
                         "the solver's comm-energy pricing (outage-aware "
                         "selection); overrides the scenario preset")
    ap.add_argument("--bits-grid", default=None,
                    help="comma-separated quantization widths (e.g. "
                         "'8,16,32'): crossed with gamma_grid into the "
                         "solver's joint (gamma, bits) decision grid "
                         "(payload gamma*S*bits/32 + I); the engine "
                         "transmits symmetric fixed-point updates at the "
                         "decided width; overrides the scenario preset")
    ap.add_argument("--mobility-sigma", type=float, default=None,
                    help="slow pathloss drift RMS in dB "
                         "(repro.core.channel.MobilityConfig); overrides "
                         "the scenario preset (0 disables)")
    ap.add_argument("--shard-clients", action="store_true",
                    help="run the fused engine sharded over a `clients` "
                         "mesh spanning all visible devices (force multiple "
                         "CPU devices with XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=K); N is ghost-padded to "
                         "mesh divisibility")
    ap.add_argument("--out", default="experiments/fl_results.json")
    a = ap.parse_args()
    from benchmarks._harness import use_compile_cache
    use_compile_cache()
    mesh = None
    if a.shard_clients:
        from repro.sharding import make_clients_mesh
        mesh = make_clients_mesh()
        print(f"sharding the client axis over {len(jax.devices())} devices")
    config_sweep = None
    swept = {"eta": a.sweep_eta, "rho": a.sweep_rho, "b_tot": a.sweep_btot}
    swept = {k: [float(x) for x in v.split(",")]
             for k, v in swept.items() if v}
    if swept:
        # cross the swept knobs into flat lanes (itertools.product order)
        import itertools
        keys = list(swept)
        lanes = list(itertools.product(*(swept[k] for k in keys)))
        config_sweep = {k: [ln[i] for ln in lanes]
                        for i, k in enumerate(keys)}
        print(f"config sweep: {len(lanes)} lanes over {keys}")
    kw = dict(out=a.out, extra_baselines=a.extra_baselines,
              eval_every=a.eval_every, mesh=mesh, scenario=a.scenario,
              deadline=a.deadline, staleness_a=a.staleness_a,
              fault_rate=a.fault_rate, crash_rate=a.crash_rate,
              churn=a.churn, defense=a.defense, clusters=a.clusters,
              pool_frac=a.pool_frac, mobility_sigma=a.mobility_sigma,
              max_retx=a.max_retx, burst_p=a.burst_p,
              price_outage=a.price_outage,
              bits_grid=([float(b) for b in a.bits_grid.split(",")]
                         if a.bits_grid else None),
              sweep_seeds=list(range(a.seeds)) if a.seeds else None,
              config_sweep=config_sweep)
    if a.paper:
        main(n_clients=50, rounds=150, **kw)
    else:
        main(n_clients=a.clients, rounds=a.rounds, **kw)
