"""Federated server: fused multi-round training on the controller API.

Round r (paper Sec. II-A + Algorithm 1):
  1. every client runs its local steps — all clients at once via a
     ``vmap`` batched client step (static local steps unrolled) that
     returns stacked flat updates [N, D] and norms ||u_i|| (no per-client
     Python loop);
  2. a *controller* (any ``repro.core.controllers`` registry entry, or a
     custom instance implementing init/decide) maps the round's
     ``RoundObservation`` to a ``RoundDecision`` (x, gamma, B);
  3. selected updates are top-k sparsified to their gamma_i and the server
     charges E_i = P_i (gamma_i S + I)/R_i(B_i);
  4. the sparse updates are combined by a fused masked |D_i|-weighted
     aggregation and applied to the global model.

Two drivers share one round body (``_make_round_core``):

* ``run_round``/``run`` — the per-round **debug path**: one jitted
  decide -> sparsify -> aggregate -> apply program per round, with host
  logging after every round;
* ``run_scanned`` — the **fused engine**: a whole chunk of rounds as one
  donated jitted ``jax.lax.scan``. Batch sampling happens in-trace from
  device-resident padded client shards (``repro.data.sample_round_batches``),
  Rayleigh fading is drawn in-jit via ``jax.random.fold_in``
  (``repro.core.channel.round_gains``), accuracy evaluation is strided
  (``eval_every``), and per-round logs come back as stacked scan outputs
  materialized on host once per chunk. Both paths draw identical batches,
  fading, and controller keys, so they produce matching trajectories
  (pinned by ``tests/test_scan_engine.py``).

``run_sweep`` vmaps the scanned engine over per-seed key sets, producing
multi-seed accuracy/energy curves at roughly single-run wall-clock — and,
with ``configs={...}``, additionally over stacked FairEnergy
hyper-parameter lanes (eta, rho, B_tot, ...): the solver reads its float
config from the carried controller state (``repro.core.fairenergy
.FEParams``), so seeds x configs share one trace and run as one jitted
program.

**Client-axis sharding** (``FederatedTrainer(..., mesh=...)``): with a
1-D ``clients`` mesh (``repro.sharding.make_clients_mesh``) the same scan
program runs under ``shard_map`` — the ``[N, L, ...]`` data stacks,
minibatch gathers, ``[N, D]`` update/sparsify buffers, and the weighted
aggregation are all shard-local, with one ``psum`` for the global model
delta. The tiny ``[N]`` observables (``u_norms``, ``h``, ``P``) are
all-gathered so controllers — whose selection/repair needs global
argsort/cumsum — run replicated and unchanged, bit-compatible with the
single-device path (``tests/test_sharded_engine.py``). Client counts that
don't divide the mesh are padded with zero-weight ghost clients
(``stack_client_datasets(..., pad_to_multiple=...)``); ghosts never enter
an observation or decision.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import ckpt as _ckpt
from repro.core.channel import (WirelessNetwork, comm_energy, comm_time,
                                round_gains)
from repro.core.controllers import (Controller, ControllerContext,
                                    RoundObservation, make_controller)
from repro.core.energy import (UNLIMITED_J, alive_mask, comp_energy,
                               comp_time)
from repro.core.faults import (DefenseConfig, FaultConfig, MeanAggregator,
                               arrival_mask, channel_estimate, corrupt_draw,
                               corrupt_payload, crash_draw, make_aggregator)
from repro.core.link import (LinkConfig, LinkState, attempt_energy,
                             attempt_outcomes, attempt_time, burst_channel,
                             burst_step, expected_attempts, init_link_state,
                             outage_probability)
from repro.core.streams import (CTRL_STREAM, FAULT_STREAM, HARVEST_STREAM,
                                LINK_STREAM, POOL_STREAM, SAMPLE_STREAM)
from repro.core.rounds import (AsyncConfig, AsyncState, apply_harvest,
                               best_case_round_time, harvest_rates,
                               init_async_state, partial_round_energy,
                               resolve_deadline, round_wall_clock,
                               staleness_weight)
from repro.data.pipeline import (client_sample_keys, sample_client_batches,
                                 sample_round_batches, stack_client_datasets)
from repro.fl import compression
from repro.fl.client import WithFrozen, make_batched_client_step
from repro.fl.updates import tree_spec, unflatten_update
from repro.core.hierarchy import HierarchyConfig, wrap_controller
from repro.sharding.fl import (CLIENTS_AXIS, async_state_specs, axis_names,
                               client_shard_count, clients_axis_size,
                               defense_state_specs, link_state_specs,
                               mesh_client_axes, replicated_specs,
                               shard_client_data)


# PRNG stream tags (folded into the per-seed base key): registered in
# repro.core.streams — one registry so two subsystems can never silently
# fold the same tag and correlate their draws (the mobility drift's
# phase stream lives in repro.core.channel off the fade key)
_CTRL_STREAM = CTRL_STREAM
_SAMPLE_STREAM = SAMPLE_STREAM
_HARVEST_STREAM = HARVEST_STREAM
_FAULT_STREAM = FAULT_STREAM
_POOL_STREAM = POOL_STREAM  # hierarchy candidate-pool sampler base key
_LINK_STREAM = LINK_STREAM  # burst interference + outage (repro.core.link)


@dataclasses.dataclass
class RoundLog:
    round: int
    selected: np.ndarray
    gamma: np.ndarray
    bandwidth: np.ndarray
    energy: np.ndarray          # J per client — total (comm + comp)
    accuracy: float             # NaN on rounds skipped by eval_every
    loss: float
    n_selected: int
    battery: Optional[np.ndarray] = None  # J per client after the round
    #                                       (inf = unlimited)
    # --- async-round fields (None on untimed / legacy runs) -------------
    t_round: Optional[float] = None       # simulated wall-clock of this
    #                                       round (s): slowest selected
    #                                       comp+comm, capped at T_round
    made: Optional[np.ndarray] = None     # [N] bool — selected AND inside
    #                                       the deadline (aggregated)
    n_late: Optional[int] = None          # selected clients past deadline
    n_stale: Optional[int] = None         # buffered updates folded in
    # --- fault-telemetry fields (None unless fault injection or defended
    #     aggregation is active — repro.core.faults) ----------------------
    n_faulted: Optional[int] = None       # crashed + corrupted participants
    n_rejected: Optional[int] = None      # updates screened out (non-finite
    #                                       rows, or all of them on a fully
    #                                       degraded round)
    clip_frac: Optional[float] = None     # fraction of accepted updates
    #                                       norm-clipped this round
    fallback: Optional[bool] = None       # solver fallback round
    #                                       (RoundDecision.fallback)
    # --- link-reliability fields (None unless the link subsystem is
    #     active — repro.core.link) ---------------------------------------
    n_retx: Optional[int] = None          # retransmissions across selected
    #                                       clients this round
    n_outage: Optional[int] = None        # retx-exhausted clients (update
    #                                       dropped, energy still charged)
    goodput_frac: Optional[float] = None  # delivered payload bits / bits
    #                                       put on air (1.0 on an idle or
    #                                       lossless round)
    e_retx: Optional[float] = None        # J spent on retransmissions
    #                                       (beyond each first attempt)
    # --- quantized-payload fields (None unless the joint (gamma, bits)
    #     grid or device-profile default widths are active) ---------------
    bits: Optional[np.ndarray] = None     # [N] transmitted quantization
    #                                       width (0 on unselected rows)
    e_saved: Optional[float] = None       # J saved this round vs sending
    #                                       the same payload at 32 bits

    @property
    def total_energy(self) -> float:
        return float(self.energy.sum())


@dataclasses.dataclass(frozen=True)
class _AsyncRuntime:
    """Engine-facing bundle of the resolved async-round quantities
    (``repro.core.rounds.AsyncConfig`` plus the trainer's per-client
    arrays): closed over by the round core, never traced as an operand.
    ``deadline`` is the concrete T_round in seconds (``deadline_q``
    already resolved); ``rates=None`` disables harvesting."""
    deadline: float
    staleness: bool
    staleness_a: float
    t_cmp: jnp.ndarray            # [n_real] s computation time
    e_cmp: jnp.ndarray            # [n_real] J computation energy
    cap: jnp.ndarray              # [n_real] J battery capacity (inf ok)
    rates: Optional[jnp.ndarray]  # [n_real] J/round mean harvest, or None
    b_tot: float
    gamma_floor: float
    s_bits: float
    i_bits: float
    n0: float


@dataclasses.dataclass(frozen=True)
class _FaultsRuntime:
    """Engine-facing bundle of the resolved fault-injection quantities
    (``repro.core.faults.FaultConfig`` plus the trainer's per-client
    timing/energy arrays and channel scalars): closed over by the round
    core, never traced as an operand. The rate/mode knobs are Python
    floats — a zero rate compiles that fault stream away entirely."""
    crash_rate: float
    corrupt_rate: float
    corrupt_mode: str
    corrupt_scale: float
    h_err_std: float
    churn_dwell: int
    churn_away: float
    t_cmp: jnp.ndarray            # [n_real] s computation time
    e_cmp: jnp.ndarray            # [n_real] J computation energy
    b_tot: float
    s_bits: float
    i_bits: float
    n0: float


@dataclasses.dataclass(frozen=True)
class _LinkRuntime:
    """Engine-facing bundle of the resolved link-reliability quantities
    (``repro.core.link.LinkConfig`` plus the trainer's per-client
    timing/energy arrays and channel scalars): closed over by the round
    core, never traced as an operand. The knobs are Python scalars — a
    disabled stream (``outage=False`` or ``bursty=False``) compiles away
    entirely."""
    outage: bool
    margin: float                 # linear fade margin 10^(dB/10)
    max_retx: int
    backoff_s: float
    bursty: bool
    burst_p: float
    burst_q: float
    noise_rise: float             # (N0 + I_burst) / N0 >= 1
    observe_burst: bool
    price_outage: bool
    t_cmp: jnp.ndarray            # [n_real] s computation time
    e_cmp: jnp.ndarray            # [n_real] J computation energy
    b_tot: float
    s_bits: float
    i_bits: float
    n0: float


@dataclasses.dataclass(frozen=True)
class _QuantRuntime:
    """Engine-facing bundle of the quantized-payload quantities: the
    per-client fallback width (what a controller without the joint
    (gamma, bits) grid transmits at — 32 everywhere unless the device
    profile carries tier defaults), the channel scalars the
    payload-equivalent re-charge and the ``e_saved`` counterfactual
    need, and the per-client computation energy. Closed over by the
    round core, never traced as an operand; ``None`` compiles the exact
    legacy full-precision program."""
    default_bits: jnp.ndarray     # [n_real] width when RoundDecision.bits
    #                               is None (non-joint controllers)
    e_cmp: jnp.ndarray            # [n_real] J computation energy
    b_tot: float
    s_bits: float
    i_bits: float
    n0: float


def _make_round_core(*, controller: Controller, spec, weights: jnp.ndarray,
                     server_lr: float, use_pallas: Optional[bool] = None,
                     block: int = compression.DEFAULT_BLOCK,
                     skip_full_sparsify: bool = True,
                     shard_axis: Optional[str] = None,
                     n_real: Optional[int] = None,
                     async_rt: Optional[_AsyncRuntime] = None,
                     fault_rt: Optional[_FaultsRuntime] = None,
                     aggregator=None,
                     link_rt: Optional[_LinkRuntime] = None,
                     quant_rt: Optional["_QuantRuntime"] = None):
    """Pure decide -> sparsify -> aggregate -> apply round body.

    Closes over the controller (its ``decide`` must be traceable), the
    pytree spec of the model, and the static |D_i| aggregation weights.
    Returns ``core(params, updates, u_norms, h, P, r, key, ctrl_state)
    -> (new_params, RoundDecision, ctrl_state)`` — traceable, shared by
    the per-round jit and the multi-round scan.

    With ``shard_axis``, the core runs *inside a shard_map shard* of the
    client axis: ``updates``/``u_norms`` are the device-local
    ``[n_local, D]``/``[n_local]`` chunk (``weights`` stays the full,
    possibly ghost-padded ``[N_pad]`` vector, replicated by closure). The
    tiny ``u_norms`` are all-gathered and sliced to the ``n_real`` true
    clients, the controller decides on the same global ``[n_real]``
    observation as the single-device path (replicated — selection masks
    are identical), and the decision's x/gamma are sliced back to the
    local chunk for the shard-local sparsify + weighted partial
    aggregation; one ``psum`` pair yields the global model delta.

    ``battery`` (an optional trailing [n_real] operand, replicated like
    the other observables) threads per-client battery charge through the
    round: depleted clients (charge <= 0) enter the observation as
    ``alive=False``, and — mirroring the ghost-client path — the engine
    hard-masks them out of the decision regardless of what the
    controller returned, so no controller can spend a dead client's
    energy. Selected clients are then debited their round energy
    (comm + comp; inf capacity never depletes). When ``battery`` is
    passed the core returns a 4-tuple ``(params, dec, state, battery)``;
    without it, the legacy 3-tuple.

    ``async_rt`` (an ``_AsyncRuntime``, requires ``battery``) activates
    the time-aware round model (``repro.core.rounds``): deadline-
    infeasible clients join the hard ``alive`` mask, selected clients
    whose realized comp+comm exceeds the deadline are dropped from the
    aggregate (charged partial energy — or full, with staleness, since
    their transmission completes in the background and lands in the
    ``astate`` stale buffer), batteries recharge via the harvesting
    draw, and the core returns ``(params, dec, state, battery, astate,
    extras)`` with ``extras = dict(t_wall, made, n_late, n_stale)``.
    When ``async_rt is None`` the emitted program is *identical* to the
    legacy one — the backward-compat contract the goldens pin.

    ``fault_rt`` (a ``_FaultsRuntime``, requires ``battery`` and the
    ``fkey`` operand) injects the ``repro.core.faults`` streams: churn
    joins the hard ``alive`` mask (with the controller's
    ``reset_clients`` hook on arrivals), the controller observes
    ``h_est`` while the realized energy is re-charged at the true
    channel, crashed clients drop from the aggregate with
    ``partial_round_energy`` proration, and corrupted payloads hit the
    post-sparsify updates shard-local. ``aggregator`` routes the combine
    step (default: the legacy ``"mean"`` weighted mean, bit-identical to
    the inline code it replaced; a ``DefenseConfig``-enabled
    ``"defended"`` aggregator screens/clips/trims and threads its
    ``fstate`` carry). With either faults or an enabled defense the core
    returns a 7-tuple ``(params, dec, state, battery, astate, fstate,
    extras)`` whose extras additionally carry the ``n_faulted /
    n_rejected / clip_frac / fallback`` telemetry lanes, and a
    non-finite aggregate is rejected wholesale (params carry unchanged,
    every participant counted rejected) instead of poisoning the scan.

    ``link_rt`` (a ``_LinkRuntime``, requires ``battery`` and the
    ``lstate``/``lkey`` operands) activates the ``repro.core.link``
    wireless-reliability model: the Gilbert-Elliott burst chain derates
    the *physics* channel (the controller optionally keeps the quiet-
    state belief), each selected client's transmission fails per attempt
    with its Rayleigh-outage probability and retries up to ``max_retx``
    times — every attempt charging real airtime and energy, deadline-
    blowing retries resolving through the async late path — and
    retx-exhausted clients are dropped from the aggregate while their
    energy and fairness-EMA effects land honestly. ``price_outage``
    hands the controller the expected-attempt comm-energy factor via
    ``RoundObservation.e_scale``. The core then returns an 8-tuple
    ``(params, dec, state, battery, astate, fstate, lstate, extras)``
    whose extras add the ``n_retx / n_outage / goodput_frac / e_retx``
    lanes. When ``link_rt is None`` the emitted program is *identical*
    to the legacy one — the backward-compat contract the goldens pin.

    ``quant_rt`` (a ``_QuantRuntime``) activates the quantized-payload
    path: every selected client's post-sparsify update rows are
    symmetrically quantized at the transmitted width — the solver's
    joint (gamma, bits) decision when ``RoundDecision.bits`` is carried,
    else the profile's per-client default — and immediately dequantized
    (``repro.fl.compression.quantize_rows``), so the psum / defended
    aggregation paths consume plain float rows unchanged. Every realized
    comm time/energy charges the payload-equivalent gamma
    ``gamma*bits/32`` (controllers without the joint grid are re-charged
    at the default width), and the extras gain the per-round ``bits``
    lane plus the ``e_saved`` counterfactual (J vs a 32-bit payload at
    the same allocation). Note the quantizer cannot encode NaN/Inf: a
    non-finite *local* update row is zeroed on the wire (in-transit
    ``corrupt_payload`` faults are applied after quantization and still
    reach the aggregator's screen). ``None`` compiles the exact legacy
    program — the same goldens contract as every other subsystem.
    """
    sharded = shard_axis is not None
    # the client axis may live on one mesh axis (legacy 1-D) or two
    # (hierarchy (clusters, clients)); a plain string stays a plain
    # string all the way into the collectives so the 1-D program is
    # byte-identical to the historical one
    axes = axis_names(shard_axis) if sharded else ()
    ax_all = (shard_axis if isinstance(shard_axis, str)
              else (axes[0] if len(axes) == 1 else axes))
    n_pad = int(weights.shape[0])
    faulty = fault_rt is not None
    agg_obj = aggregator if aggregator is not None else MeanAggregator()
    defended = bool(getattr(agg_obj, "enabled", False))
    telemetry = faulty or defended
    linky = link_rt is not None
    link_out = linky and link_rt.outage
    link_burst = linky and link_rt.bursty
    quant = quant_rt is not None

    def _psum_stages(x):
        """Two-tier reduction: innermost (clients) axis first — the
        cluster-head partial aggregate — then the clusters axis — the
        server reduction. On a 1-D mesh this is exactly the legacy
        single psum."""
        for a in reversed(axes):
            x = jax.lax.psum(x, a)
        return x

    def _flat_index():
        """This shard's position along the flattened (cluster-major)
        client axis — ``axis_index`` on 1-D, row-major compose on 2-D."""
        idx = jax.lax.axis_index(axes[0])
        for a in axes[1:]:
            idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
        return idx

    def _local(vec, fill, i0, n_local):
        """Pad an [n_real] vector with ghost rows and slice this shard's
        chunk (identity layout when unsharded: n_pad == n_real, i0 = 0)."""
        return jax.lax.dynamic_slice_in_dim(
            jnp.pad(vec, (0, n_pad - n_real), constant_values=fill),
            i0, n_local)

    def core(params, updates, u_norms, h, P, r, key, ctrl_state,
             battery=None, astate=None, hkey=None, fstate=None, fkey=None,
             lstate=None, lkey=None):
        if async_rt is not None and battery is None:
            raise ValueError("the async round model needs the battery "
                             "carry (pass battery=jnp.full(n, inf) for "
                             "unlimited capacities)")
        if faulty and (battery is None or fkey is None):
            raise ValueError("fault injection needs the battery carry and "
                             "the fault key operand (pass battery="
                             "jnp.full(n, inf) for unlimited capacities)")
        if linky and (battery is None or lkey is None):
            raise ValueError("the link-reliability model needs the battery "
                             "carry and the link key operand (pass battery="
                             "jnp.full(n, inf) for unlimited capacities)")
        if quant and battery is None:
            raise ValueError("the quantized-payload path needs the battery "
                             "carry (pass battery=jnp.full(n, inf) for "
                             "unlimited capacities)")
        if sharded:
            n_local = u_norms.shape[0]
            i0 = _flat_index() * n_local
            obs_norms = jax.lax.all_gather(u_norms, ax_all,
                                           tiled=True)[:n_real]
        else:
            n_local = u_norms.shape[0]
            i0 = jnp.int32(0)
            obs_norms = u_norms
        n_obs = obs_norms.shape[0]
        if link_burst:
            # one Gilbert-Elliott transition per round (uniforms pure in
            # (link key, round); the chain itself is the carried lstate).
            # The burst state derates the *physics* channel — a raised
            # noise floor is exactly a scaled gain
            # (repro.core.link.burst_channel) — so every realized comm
            # time/energy below pays the interference
            burst = burst_step(lkey, r, lstate.burst, link_rt.burst_p,
                               link_rt.burst_q)
            lstate = LinkState(burst=burst)
            h_phys = burst_channel(h, burst, link_rt.noise_rise)
        else:
            h_phys = h
        # the controller's channel belief: the quiet-state channel unless
        # it observes the burst (LinkConfig.observe_burst), then
        # lognormal-noised if the channel-estimate fault stream is on;
        # the realized transmission below always uses the physics channel
        h_obs = h_phys if (link_burst and link_rt.observe_burst) else h
        if faulty and fault_rt.h_err_std > 0.0:
            h_obs = channel_estimate(fkey, r, h_obs, fault_rt.h_err_std)
        h = h_phys
        present = arrived = None
        if faulty and fault_rt.churn_dwell > 0:
            present, arrived = arrival_mask(fkey, r, n_obs,
                                            fault_rt.churn_away,
                                            fault_rt.churn_dwell)
        alive = alive_mask(battery) if battery is not None else None
        if present is not None:
            # departed clients join the hard mask: never observed as
            # selectable, never selected, never charged
            alive = alive & present
            if hasattr(controller, "reset_clients"):
                # (re)arrivals get fresh per-client controller state — a
                # returning slot must not inherit the departed occupant's
                # fairness debt
                ctrl_state = controller.reset_clients(ctrl_state, arrived)
        t_obs = None
        if async_rt is not None:
            # best-case round time: a client that cannot make the deadline
            # under ANY allocation is priced out through the same hard
            # mask as a depleted battery — controllers stay unchanged
            t_obs = best_case_round_time(
                async_rt.t_cmp, P, h_obs, b_tot=async_rt.b_tot,
                gamma_floor=async_rt.gamma_floor, s_bits=async_rt.s_bits,
                i_bits=async_rt.i_bits, n0=async_rt.n0)
            alive = alive & (t_obs <= async_rt.deadline)
        p_out = e_scale = None
        if link_out:
            # per-attempt outage probability at the decided operating
            # point: the belief h_obs sets the design SNR, the physics h
            # the realized fade mean. The (b, gamma) dependence cancels
            # (both SNRs are taken at the same allocation), so p_out is a
            # per-client scalar — decision-free, priceable *before* the
            # decide
            p_out = outage_probability(h_obs, h, link_rt.margin)
            if link_rt.price_outage:
                e_scale = expected_attempts(p_out)
        obs = RoundObservation(u_norms=obs_norms, h=h_obs, P=P, round=r,
                               key=key, alive=alive, t_round=t_obs,
                               e_scale=e_scale)
        with jax.named_scope("fl.decide"):
            dec, new_state = controller.decide(obs, ctrl_state)
        if battery is not None:
            # hard mask, whatever the controller decided: a depleted
            # client transmits nothing and is charged nothing
            x = dec.x & alive
            mf = x.astype(jnp.float32)
            dec = dec._replace(x=x, gamma=dec.gamma * mf,
                               bandwidth=dec.bandwidth * mf,
                               energy=dec.energy * mf,
                               bw_used=jnp.sum(dec.bandwidth * mf))
        bits_w = bits_fac = None
        if quant:
            # transmitted quantization width: the solver's joint decision
            # when the grid is widened (RoundDecision.bits), else the
            # device-profile default; 32 on unselected rows so their
            # zero-weight lanes stay inert
            bits_dec = (dec.bits if dec.bits is not None
                        else quant_rt.default_bits)
            bits_w = jnp.where(dec.x, bits_dec, 32.0)
            bits_fac = bits_w / 32.0
            if dec.bits is None:
                # the controller priced a full 32-bit payload but the
                # wire carries the default width — re-charge the comm
                # energy at the payload-equivalent gamma (same
                # allocation, realized channel). b/gamma guards as in
                # the re-charge block below
                b_q = jnp.where(dec.x, dec.bandwidth, quant_rt.b_tot)
                g_q = jnp.where(dec.x, dec.gamma, 1.0)
                dec = dec._replace(energy=dec.x.astype(jnp.float32) * (
                    comm_energy(g_q * bits_fac, b_q, P, h, quant_rt.s_bits,
                                quant_rt.i_bits, quant_rt.n0)
                    + quant_rt.e_cmp))

        def _pay(g):
            # payload-equivalent gamma: a bits-wide payload occupies
            # gamma*bits/32 of the full-precision one, so every channel
            # helper is reused unchanged; identity when quantization is
            # off (no extra ops — the legacy program is untouched)
            return g * bits_fac if quant else g

        if (battery is not None and async_rt is None and not faulty
                and not linky):
            # debit the round's spend; the depleting transmission is
            # allowed to finish (brownout), charge floors at 0 so the
            # carried state stays in [0, capacity] (inf stays inf)
            battery = jnp.maximum(battery - dec.energy, 0.0)
        if (faulty and fault_rt.h_err_std > 0.0) or (link_burst
                                                     and not link_out):
            # the controller priced energy at its belief (h_est, and/or
            # the quiet-state channel under unobserved burst-only
            # interference); the transmission realizes on the physics
            # channel — re-charge at true h (same allocation). With the
            # outage model on, the retx accounting below re-prices the
            # whole energy instead. b/gamma guards mirror
            # masked_decision: comm_energy is inf below the 1 Hz floor
            # and the unselected-lane inf*0 would otherwise NaN
            _rt = fault_rt if faulty else link_rt
            b_safe = jnp.where(dec.x, dec.bandwidth, _rt.b_tot)
            g_safe = jnp.where(dec.x, dec.gamma, 1.0)
            e_real = dec.x.astype(jnp.float32) * (
                comm_energy(_pay(g_safe), b_safe, P, h, _rt.s_bits,
                            _rt.i_bits, _rt.n0) + _rt.e_cmp)
            dec = dec._replace(energy=e_real)
        crashed = cfrac = None
        if faulty and fault_rt.crash_rate > 0.0:
            crashed_m, cfrac = crash_draw(fkey, r, n_obs,
                                          fault_rt.crash_rate)
            crashed = dec.x & crashed_m

        # ---- bounded-HARQ retransmission accounting (repro.core.link):
        # each attempt is a full airtime of the decided allocation; a
        # backoff slot precedes each retry. The realized per-client cost
        # replaces the controller's priced energy wholesale (the priced
        # value was an expectation; this is the draw) ----
        attempts_f = delivered = lost_m = t_link = e_retx_vec = None
        if link_out:
            b_safe_l = jnp.where(dec.x, dec.bandwidth, link_rt.b_tot)
            g_safe_l = jnp.where(dec.x, dec.gamma, 1.0)
            t1 = comm_time(_pay(g_safe_l), b_safe_l, P, h, link_rt.s_bits,
                           link_rt.i_bits, link_rt.n0)
            attempts, delivered = attempt_outcomes(lkey, r, p_out,
                                                   link_rt.max_retx)
            attempts_f = attempts.astype(jnp.float32)
            t_link = attempt_time(attempts_f, t1, link_rt.backoff_s)
            xf_l = dec.x.astype(jnp.float32)
            e_link = xf_l * (attempt_energy(attempts_f, t1, P)
                             + link_rt.e_cmp)
            e_retx_vec = xf_l * (attempts_f - 1.0) * P * t1
            dec = dec._replace(energy=e_link)
            # a crashed client is counted as a crash, not an outage: its
            # energy is prorated by the crash machinery below and its
            # retx telemetry is dropped with it
            lost_m = dec.x & ~delivered
            if crashed is not None:
                lost_m = lost_m & ~crashed

        made = late = extras = None
        if async_rt is not None:
            # realized per-client round time under the controller's actual
            # allocation (comm_time is inf on unselected B=0 rows — only
            # ever read through the selection mask)
            t_comm = comm_time(_pay(dec.gamma), dec.bandwidth, P, h,
                               async_rt.s_bits, async_rt.i_bits, async_rt.n0)
            if link_out:
                # the realized timeline is the whole retry sequence
                # (attempts x airtime + backoff slots); deadline-blowing
                # retries resolve through the existing late path below
                t_comm = t_link
            t_total = async_rt.t_cmp + t_comm
            feasible = dec.x & (t_total <= async_rt.deadline)
            # a crashed client is neither made nor late: its update never
            # reaches the server and its background transmission (if any)
            # never completes (identical to legacy when crashed is None,
            # since x & f & ~(x & c) == x & f & ~c)
            made = feasible if crashed is None else feasible & ~crashed
            late = (dec.x & ~feasible if crashed is None
                    else dec.x & ~feasible & ~crashed)
            if delivered is not None:
                # a retx-exhausted client is neither made nor
                # late-buffered — its update never decodes — but it pays
                # like a late one (the airtime was real)
                made = made & delivered
                late = late & delivered
            e_full = dec.energy
            if not async_rt.staleness:
                # a dropped update is abandoned at the deadline: charge
                # computation first, then the prorated transmission (the
                # minimum() keeps partial <= full under fp rounding).
                # Exhausted clients inside the deadline ran their full
                # retry budget: e_part equals the full charge there
                drop = late if lost_m is None else late | lost_m
                e_part = partial_round_energy(async_rt.t_cmp, t_comm,
                                              async_rt.e_cmp, P,
                                              async_rt.deadline)
                dec = dec._replace(energy=jnp.where(
                    made, dec.energy,
                    jnp.where(drop, jnp.minimum(e_part, dec.energy), 0.0)))
            # with staleness the transmission completes in the background,
            # so late clients pay their full round energy
            if crashed is not None:
                # a crashed client dies at the uniform fraction cfrac of
                # its own round (capped at the deadline abandon unless the
                # transmission would have continued in the background):
                # computation first, then prorated transmission
                # (partial_round_energy is monotone in its deadline, so
                # the cap and the fp-safety minimum compose exactly)
                t_cap = (t_total if async_rt.staleness
                         else jnp.minimum(t_total, async_rt.deadline))
                t_c = cfrac * jnp.where(dec.x, t_cap, 0.0)
                e_crash = partial_round_energy(async_rt.t_cmp, t_comm,
                                               async_rt.e_cmp, P, t_c)
                dec = dec._replace(energy=jnp.where(
                    crashed, jnp.minimum(e_crash, e_full), dec.energy))
            battery = jnp.maximum(battery - dec.energy, 0.0)
            battery = apply_harvest(battery, async_rt.cap, hkey, r,
                                    async_rt.rates)
            t_wall = round_wall_clock(dec.x, t_total, async_rt.deadline)
            extras = dict(t_wall=t_wall, made=made,
                          n_late=jnp.sum(late.astype(jnp.int32)),
                          n_stale=jnp.int32(0))
        elif faulty or linky:
            if crashed is not None:
                # untimed rounds still prorate crash energy over the
                # client's own comp+comm duration (guards as above: the
                # unselected-lane comm_time would be inf); with the
                # outage model on, the duration is the link-extended
                # retry timeline
                if link_out:
                    t_comm_f = t_link
                else:
                    t_comm_f = comm_time(_pay(jnp.where(dec.x, dec.gamma,
                                                        1.0)),
                                         jnp.where(dec.x, dec.bandwidth,
                                                   fault_rt.b_tot),
                                         P, h, fault_rt.s_bits,
                                         fault_rt.i_bits, fault_rt.n0)
                t_c = cfrac * jnp.where(dec.x, fault_rt.t_cmp + t_comm_f,
                                        0.0)
                e_crash = partial_round_energy(fault_rt.t_cmp, t_comm_f,
                                               fault_rt.e_cmp, P, t_c)
                dec = dec._replace(energy=jnp.where(
                    crashed, jnp.minimum(e_crash, dec.energy), dec.energy))
            # the deferred legacy debit (see the hard-mask block above)
            battery = jnp.maximum(battery - dec.energy, 0.0)

        # only clients inside the deadline (and not crashed) enter this
        # round's aggregate
        part_glob = made if made is not None else dec.x
        if crashed is not None and made is None:
            part_glob = dec.x & ~crashed
        if delivered is not None and made is None:
            # untimed path: a retx-exhausted update never decodes, so it
            # never enters the aggregate (graceful degradation — the
            # energy and fairness-EMA effects above already landed)
            part_glob = part_glob & delivered
        xf = part_glob.astype(jnp.float32)
        cm = fl_u = None
        if faulty and fault_rt.corrupt_rate > 0.0:
            # corruption hits the transmitted payload of participating
            # clients — drawn globally (replicated masks), applied to the
            # shard-local sparse matrix below
            cm, fl_u = corrupt_draw(fkey, r, n_obs, fault_rt.corrupt_rate)
        # unselected rows carry zero aggregation weight, so their sparsity
        # level is irrelevant — treat them as gamma=1 so full-precision
        # rounds (every *selected* gamma == 1) skip the sparsify pass;
        # late rows keep their gamma: the buffered update must be the
        # sparsified payload the client actually transmits
        gamma = jnp.where(dec.x, jnp.clip(dec.gamma, 1e-6, 1.0), 1.0)
        if sharded:
            # ghost rows: never selected (x=0), gamma=1 keeps the skip-full
            # fast path available; then take this shard's local chunk
            xf = _local(xf, 0.0, i0, n_local)
            gamma = _local(gamma, 1.0, i0, n_local)
            w_data = jax.lax.dynamic_slice_in_dim(weights, i0, n_local)
        else:
            w_data = weights
        with jax.named_scope("fl.sparsify"):
            sparse = compression.batch_block_topk(
                updates, gamma, block=block, use_pallas=use_pallas,
                skip_full=skip_full_sparsify)
        if quant:
            # client-side symmetric fixed-point quantization of the
            # sparse payload at the transmitted width, dequantized right
            # back (repro.fl.compression.quantize_rows) so the psum /
            # defended-screen paths below consume plain float rows.
            # Ordered before corrupt_payload: in-transit corruption hits
            # the already-quantized wire stream — a real quantized
            # payload cannot carry NaN, so the quantizer's finite screen
            # must not mask injected faults
            bits_l = (_local(bits_w, 32.0, i0, n_local) if sharded
                      else bits_w)
            sparse = compression.quantize_rows(sparse, bits_l)
        if cm is not None:
            if sharded:
                cm_l = _local(cm, False, i0, n_local)
                fl_l = _local(fl_u, 0.0, i0, n_local)
            else:
                cm_l, fl_l = cm, fl_u
            sparse = corrupt_payload(sparse, cm_l, fl_l,
                                     fault_rt.corrupt_mode,
                                     fault_rt.corrupt_scale)
        # combine through the aggregator layer: the default "mean" emits
        # exactly the legacy weighted-mean ops; a defended aggregator
        # screens/clips/trims shard-local and returns the cleaned sparse
        # matrix (what the staleness buffer must hold) plus its stats
        with jax.named_scope("fl.aggregate"):
            partial, wsum, fstate, dstats, sparse = agg_obj(
                sparse, xf, w_data, fstate,
                axis=ax_all if sharded else None,
                n_shards=n_pad // n_local)                  # [D], scalar
            if async_rt is not None and async_rt.staleness:
                # ---- staleness-weighted buffered aggregation
                # (shard-local): age the pending slots by this round's
                # wall-clock, fold the ones whose background transmission
                # has completed into the aggregate with the w(tau)
                # discount, then buffer this round's late updates (one
                # slot per client — a newer late update replaces an
                # older, staler one)
                buf, age, t_rem = astate
                pending = age >= 0
                age = jnp.where(pending, age + 1, age)
                t_rem = jnp.where(pending, t_rem - extras["t_wall"], t_rem)
                ready = pending & (t_rem <= 0.0)
                w_stale = (w_data * staleness_weight(age, async_rt.staleness_a)
                           * ready.astype(jnp.float32))
                wsum = wsum + jnp.sum(w_stale)
                partial = partial + w_stale @ buf
                late_l = (_local(late.astype(jnp.float32), 0.0, i0,
                                 n_local) > 0.0 if sharded else late)
                t_new = jnp.clip(t_total - async_rt.deadline, 0.0, None)
                t_new_l = _local(t_new, 0.0, i0, n_local) if sharded else t_new
                buf = jnp.where(late_l[:, None], sparse, buf)
                age = jnp.where(late_l, 0, jnp.where(ready, -1, age))
                t_rem = jnp.where(late_l, t_new_l,
                                  jnp.where(ready, 0.0, t_rem))
                astate = AsyncState(buf=buf, age=age, t_rem=t_rem)
                n_stale = jnp.sum(ready.astype(jnp.int32))
                if sharded:
                    n_stale = _psum_stages(n_stale)
                extras["n_stale"] = n_stale
            if sharded:
                wsum = _psum_stages(wsum)
                partial = _psum_stages(partial)
            agg = partial / jnp.maximum(wsum, 1e-12) * server_lr
            agg = jnp.where(wsum > 0.0, agg, jnp.zeros_like(agg))
        if telemetry:
            n_part = jnp.sum(part_glob.astype(jnp.int32))
            n_rej = dstats.get("n_rejected", jnp.int32(0))
            n_clip = dstats.get("n_clipped", jnp.int32(0))
            if sharded and dstats:
                n_rej = _psum_stages(n_rej)
                n_clip = _psum_stages(n_clip)
            # last-resort guard: whatever slipped past the defenses (or
            # an undefended run's corrupted payloads) must not poison the
            # donated params carry forever — reject the whole round and
            # count every accepted participant as rejected
            ok_round = jnp.all(jnp.isfinite(agg))
            agg = jnp.where(ok_round, agg, jnp.zeros_like(agg))
            n_rej = n_rej + jnp.where(ok_round, jnp.int32(0),
                                      jnp.maximum(n_part - n_rej, 0))
            nf = jnp.int32(0)
            if crashed is not None:
                nf = nf + jnp.sum(crashed.astype(jnp.int32))
            if cm is not None:
                nf = nf + jnp.sum((cm & part_glob).astype(jnp.int32))
            clip_frac = (n_clip.astype(jnp.float32)
                         / jnp.maximum(n_part - n_rej, 1).astype(jnp.float32))
            fextras = dict(
                n_faulted=nf, n_rejected=n_rej, clip_frac=clip_frac,
                fallback=jnp.asarray(dec.fallback, jnp.bool_))
        with jax.named_scope("fl.aggregate"):
            delta_tree = unflatten_update(agg, spec)
            new_params = jax.tree_util.tree_map(
                lambda p, d: p + d.astype(p.dtype), params, delta_tree)
        if quant:
            # e_saved counterfactual: what the same (gamma, B) allocation
            # would have cost at a full 32-bit payload minus the realized
            # quantized single-attempt charge (retransmission multiples
            # scale both sides equally and are excluded)
            b_q = jnp.where(dec.x, dec.bandwidth, quant_rt.b_tot)
            g_q = jnp.where(dec.x, dec.gamma, 1.0)
            de = (comm_energy(g_q, b_q, P, h, quant_rt.s_bits,
                              quant_rt.i_bits, quant_rt.n0)
                  - comm_energy(_pay(g_q), b_q, P, h, quant_rt.s_bits,
                                quant_rt.i_bits, quant_rt.n0))
            qextras = dict(bits=jnp.where(dec.x, bits_w, 0.0),
                           e_saved=jnp.sum(dec.x.astype(jnp.float32) * de))
        if linky:
            if link_out:
                # link telemetry over non-crashed selected clients (a
                # crash is accounted as a crash, not link loss); goodput
                # is link-layer: a delivered-but-late payload still
                # decoded, only exhausted ones are dead air
                nc_f = (xf_l if crashed is None
                        else xf_l * (~crashed).astype(jnp.float32))
                ok_m = dec.x & delivered
                if crashed is not None:
                    ok_m = ok_m & ~crashed
                d_bits = _pay(g_safe_l) * link_rt.s_bits + link_rt.i_bits
                tx_bits = jnp.sum(nc_f * attempts_f * d_bits)
                ok_bits = jnp.sum(jnp.where(ok_m, d_bits, 0.0))
                lextras = dict(
                    n_retx=jnp.sum(nc_f * (attempts_f - 1.0)
                                   ).astype(jnp.int32),
                    n_outage=jnp.sum(lost_m.astype(jnp.int32)),
                    goodput_frac=jnp.where(
                        tx_bits > 0.0,
                        ok_bits / jnp.maximum(tx_bits, 1e-30), 1.0),
                    e_retx=jnp.sum(nc_f * e_retx_vec))
            else:
                # burst-only mode: single lossless attempt per selection
                lextras = dict(n_retx=jnp.int32(0), n_outage=jnp.int32(0),
                               goodput_frac=jnp.float32(1.0),
                               e_retx=jnp.float32(0.0))
            ext = dict(extras) if extras is not None else {}
            if telemetry:
                ext.update(fextras)
            ext.update(lextras)
            if quant:
                ext.update(qextras)
            return (new_params, dec, new_state, battery, astate, fstate,
                    lstate, ext)
        if telemetry:
            ext = dict(extras) if extras is not None else {}
            ext.update(fextras)
            if quant:
                ext.update(qextras)
            return (new_params, dec, new_state, battery, astate, fstate,
                    ext)
        if async_rt is not None:
            if quant:
                extras = dict(extras, **qextras)
            return new_params, dec, new_state, battery, astate, extras
        if quant:
            return new_params, dec, new_state, battery, qextras
        if battery is not None:
            return new_params, dec, new_state, battery
        return new_params, dec, new_state

    return core


def make_round_engine(*, controller: Controller, spec, weights: jnp.ndarray,
                      server_lr: float, use_pallas: Optional[bool] = None,
                      block: int = compression.DEFAULT_BLOCK,
                      skip_full_sparsify: bool = True,
                      fault_rt: Optional[_FaultsRuntime] = None,
                      aggregator=None):
    """Jitted single-round engine (standalone / back-compat API)."""
    return jax.jit(_make_round_core(
        controller=controller, spec=spec, weights=weights,
        server_lr=server_lr, use_pallas=use_pallas, block=block,
        skip_full_sparsify=skip_full_sparsify, fault_rt=fault_rt,
        aggregator=aggregator))


def make_scan_engine(*, controller: Controller, spec, weights: jnp.ndarray,
                     server_lr: float, client_step, eval_fn,
                     pathloss: jnp.ndarray, P: jnp.ndarray, rayleigh: bool,
                     local_steps: int, batch: int,
                     use_pallas: Optional[bool] = None,
                     block: int = compression.DEFAULT_BLOCK, unroll: int = 1,
                     mesh=None, mesh_axis: str = CLIENTS_AXIS,
                     n_real: Optional[int] = None,
                     async_rt: Optional[_AsyncRuntime] = None,
                     fault_rt: Optional[_FaultsRuntime] = None,
                     aggregator=None, mobility=None,
                     link_rt: Optional[_LinkRuntime] = None,
                     quant_rt: Optional[_QuantRuntime] = None):
    """Builds the fused multi-round scan program.

    Returns ``scan_fn(params, ctrl_state, battery, astate, fstate,
    lstate, data, keys, start_round, last_round, eval_every, n_rounds)``
    executing
    ``n_rounds`` (static) FL rounds as one ``lax.scan``: traced fading +
    batch sampling + client vmap step + decide/sparsify/aggregate/apply
    + battery debit + strided eval. ``battery`` is the [n_real]
    per-client charge (J) carried across rounds — pass
    ``jnp.full(n, inf)`` for the unlimited (legacy) physics, which is
    bit-identical to the battery-free engine. ``astate`` is the async
    carry: ``()`` unless staleness buffering is on (then a
    ``repro.core.rounds.AsyncState`` — shard-local under a mesh); an
    empty ``()`` contributes no leaves, so the compiled program is the
    legacy one. ``fstate`` is the defended-aggregation carry on the same
    contract (``()`` unless the aggregator tracks a clip quantile —
    ``repro.core.faults.DefenseState``, replicated under a mesh), and
    ``lstate`` the link-reliability carry (``()`` unless the
    Gilbert-Elliott burst chain is on — ``repro.core.link.LinkState``,
    replicated under a mesh). ``keys`` is ``dict(fade=..., sample=...,
    ctrl=..., harvest=..., fault=..., link=...)`` PRNG keys (unused
    streams are dead code the compiler drops); ``eval_every`` is a
    traced int (accuracy is NaN on skipped rounds; the ``last_round``
    index is always evaluated). Outputs are stacked per-round logs
    (including the per-round ``battery`` trace, plus
    ``t_round``/``made``/``n_late``/``n_stale`` when ``async_rt``
    is set, plus ``n_faulted``/``n_rejected``/``clip_frac``/``fallback``
    when fault injection or a defended aggregator is active, plus
    ``n_retx``/``n_outage``/``goodput_frac``/``e_retx`` when the link
    subsystem is, plus ``bits``/``e_saved`` when the quantized-payload
    path is). ``frozen`` (keyword, default None) is a frozen model base
    that ``client_step`` takes as its third argument and ``eval_fn``
    reads through ``WithFrozen(params, frozen)``; it is an operand, never
    part of the carry, and never a constant of the program. Wrap in
    ``jax.jit(..., static_argnames="n_rounds",
    donate_argnums=(0, 1, 2, 3, 4, 5))`` — or ``vmap`` over ``keys``
    for sweeps.

    With ``mesh`` (a 1-D mesh carrying ``mesh_axis``), the whole scan is
    wrapped in ``shard_map``: ``data`` comes in sharded on its client
    axis (``repro.sharding.shard_client_data``; the padded client count
    must divide the mesh), sampling / client step / sparsify /
    aggregation run shard-local with one psum pair for the model delta,
    and params, controller state, keys, and the stacked per-round logs
    are replicated. ``n_real`` is the true client count — the decision
    arrays in the outputs keep that (unpadded) size.
    """
    sharded = mesh is not None
    axis = None
    axes = ()
    if sharded:
        # a hierarchy mesh carries a leading "clusters" axis: the client
        # lanes are laid out cluster-major over both mesh axes. The plain
        # string is kept on a 1-D mesh so the emitted collectives stay
        # byte-identical to the historical program.
        axes = mesh_client_axes(mesh, mesh_axis)
        axis = mesh_axis if len(axes) == 1 else axes
        n_pad = int(weights.shape[0])
        n_real = n_real if n_real is not None else n_pad
        n_dev = client_shard_count(mesh, mesh_axis)
        if n_pad % n_dev != 0:
            raise ValueError(
                f"padded client count {n_pad} does not divide the "
                f"{axes} mesh axes ({n_dev}); stack the datasets "
                f"with pad_to_multiple={n_dev}")
    core = _make_round_core(controller=controller, spec=spec, weights=weights,
                            server_lr=server_lr, use_pallas=use_pallas,
                            block=block, shard_axis=axis, n_real=n_real,
                            async_rt=async_rt, fault_rt=fault_rt,
                            aggregator=aggregator, link_rt=link_rt,
                            quant_rt=quant_rt)
    faulty = fault_rt is not None
    telemetry = faulty or bool(getattr(aggregator, "enabled", False))
    linky = link_rt is not None
    quant = quant_rt is not None

    n_pad_keys = int(weights.shape[0])
    n_real_keys = n_real if n_real is not None else n_pad_keys

    def scan_body(params, ctrl_state, battery, astate, fstate, lstate, data,
                  keys, start_round, last_round, eval_every, n_rounds: int,
                  frozen=None):
        n_local = data.lengths.shape[0]             # per-shard when sharded
        # the model's view of the params: with a frozen base, the loss and
        # eval are handed (trained, base); only the trained tree is the
        # carry that rounds update
        with_base = ((lambda q: q) if frozen is None
                     else (lambda q: WithFrozen(q, frozen)))
        base_arg = () if frozen is None else (frozen,)
        if sharded:
            i0 = jax.lax.axis_index(axes[0])
            for a in axes[1:]:
                i0 = i0 * jax.lax.psum(1, a) + jax.lax.axis_index(a)
            i0 = i0 * n_local
        else:
            i0 = jnp.int32(0)

        def step(carry, r):
            p, state, batt, ast, fst, lst = carry
            # the round's stages are named (``fl.<stage>``) in the HLO
            # metadata, so a profiler trace attributes every device op
            with jax.named_scope("fl.sample"):
                h = round_gains(keys["fade"], pathloss, r, rayleigh,
                                mobility=mobility)
                # every shard derives the full (tiny) per-client key set
                # — real clients keep the unpadded split stream — and
                # slices its local chunk: identical batches in every
                # layout
                ckeys = jax.lax.dynamic_slice_in_dim(
                    client_sample_keys(keys["sample"], r, n_real_keys,
                                       n_pad_keys), i0, n_local)
                batches = sample_client_batches(data.arrays, data.lengths,
                                                ckeys, local_steps, batch)
            with jax.named_scope("fl.client_step"):
                updates, u_norms, losses = client_step(p, batches, *base_arg)
            ckey = jax.random.fold_in(keys["ctrl"], r)
            if linky:
                p, dec, state, batt, ast, fst, lst, extras = core(
                    p, updates, u_norms, h, P, r, ckey, state, batt, ast,
                    keys.get("harvest"), fst, keys.get("fault"), lst,
                    keys.get("link"))
            elif telemetry:
                p, dec, state, batt, ast, fst, extras = core(
                    p, updates, u_norms, h, P, r, ckey, state, batt, ast,
                    keys.get("harvest"), fst, keys.get("fault"))
            elif async_rt is not None:
                p, dec, state, batt, ast, extras = core(
                    p, updates, u_norms, h, P, r, ckey, state, batt, ast,
                    keys["harvest"])
            elif quant:
                p, dec, state, batt, extras = core(
                    p, updates, u_norms, h, P, r, ckey, state, batt)
            else:
                p, dec, state, batt = core(p, updates, u_norms, h, P, r,
                                           ckey, state, batt)
            if sharded:
                losses = jax.lax.all_gather(
                    losses, axis, tiled=True)[:n_real]
            do_eval = ((r % eval_every) == 0) | (r == last_round)
            with jax.named_scope("fl.eval"):
                acc = jax.lax.cond(do_eval,
                                   lambda q: eval_fn(with_base(q)).astype(
                                       jnp.float32),
                                   lambda q: jnp.float32(jnp.nan), p)
            out = dict(x=dec.x, gamma=dec.gamma, bandwidth=dec.bandwidth,
                       energy=dec.energy, accuracy=acc,
                       loss=jnp.mean(losses), battery=batt)
            if async_rt is not None:
                out.update(t_round=extras["t_wall"], made=extras["made"],
                           n_late=extras["n_late"],
                           n_stale=extras["n_stale"])
            if telemetry:
                out.update(n_faulted=extras["n_faulted"],
                           n_rejected=extras["n_rejected"],
                           clip_frac=extras["clip_frac"],
                           fallback=extras["fallback"])
            if linky:
                out.update(n_retx=extras["n_retx"],
                           n_outage=extras["n_outage"],
                           goodput_frac=extras["goodput_frac"],
                           e_retx=extras["e_retx"])
            if quant:
                out.update(bits=extras["bits"], e_saved=extras["e_saved"])
            return (p, state, batt, ast, fst, lst), out

        rs = start_round + jnp.arange(n_rounds, dtype=jnp.int32)
        (params, ctrl_state, battery, astate, fstate, lstate), outs = \
            jax.lax.scan(
                step, (params, ctrl_state, battery, astate, fstate, lstate),
                rs, unroll=unroll)
        return params, ctrl_state, battery, astate, fstate, lstate, outs

    if not sharded:
        return scan_body

    from jax.sharding import PartitionSpec as PS

    def scan_fn(params, ctrl_state, battery, astate, fstate, lstate, data,
                keys, start_round, last_round, eval_every, n_rounds: int,
                frozen=None):
        # a frozen base rides along replicated, after the other operands
        base_arg = () if frozen is None else (frozen,)
        base_spec = () if frozen is None else (replicated_specs(frozen),)

        def body(*args):
            return scan_body(*args[:11], n_rounds=n_rounds,
                             frozen=args[11] if base_arg else None)
        # only `data` and the stale-update buffer are split (leading
        # client axis); everything else — params, controller state,
        # battery, defense state, link state, keys, round bounds, stacked
        # logs — is replicated. check_vma=False: the outputs *are*
        # replicated (built from psum/all-gather results) but the static
        # varying-axes checker cannot see that through the scan carry.
        ast_specs = async_state_specs(astate, axis)
        fst_specs = defense_state_specs(fstate)
        lst_specs = link_state_specs(lstate)
        data_entry = axes[0] if len(axes) == 1 else tuple(axes)
        sharded_fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(replicated_specs(params), replicated_specs(ctrl_state),
                      PS(), ast_specs, fst_specs, lst_specs, PS(data_entry),
                      PS(), PS(), PS(), PS()) + base_spec,
            out_specs=(replicated_specs(params), replicated_specs(ctrl_state),
                       PS(), ast_specs, fst_specs, lst_specs, PS()),
            check_vma=False)
        return sharded_fn(params, ctrl_state, battery, astate, fstate,
                          lstate, data, keys, start_round, last_round,
                          eval_every, *base_arg)

    return scan_fn


class FederatedTrainer:
    """Drives FL rounds for a given controller.

    controller: a registry name — "fairenergy" | "scoremax" | "ecorandom" |
        "randomfull" | "channelgreedy" (see
        ``repro.core.controllers.available_controllers()``) — or any object
        implementing the Controller protocol.
    ``strategy`` is accepted as a deprecated alias for ``controller``.

    Client shards live on device as padded ``[N, L, ...]`` stacks; batch
    sampling and channel fading are pure functions of (seed, round), so
    ``run_round`` (debug) and ``run_scanned`` (fused) see identical
    randomness. ``eval_fn`` must be JAX-traceable (params -> scalar).

    ``mesh``: a 1-D mesh with a ``clients`` axis (``mesh_axis``) — e.g.
    ``repro.sharding.make_clients_mesh()`` — switches the fused engine to
    client-axis sharded execution: data stacks, update/sparsify buffers,
    and the aggregation are split across devices (one psum for the global
    delta), the ``[N]`` observables stay replicated, and the client count
    is ghost-padded to mesh divisibility. Trajectories are bit-compatible
    with ``mesh=None`` (same masks; params/energy to last-ulp tolerance).

    ``device_profile``: a ``repro.core.energy.DeviceProfile`` (or a kind
    string like "tiered") attaches heterogeneous computation energy —
    priced into every controller's decisions and charged per round — and
    optional finite batteries, whose charge threads through the scan
    carry: depleted clients are masked unselectable like ghost clients.
    ``repro.scenarios`` presets compose profiles with partition/channel
    knobs. Without a profile the legacy communication-only physics is
    reproduced bit-for-bit.

    ``async_cfg``: a ``repro.core.rounds.AsyncConfig`` switches the
    engine to time-aware rounds — deadline drops with partial energy,
    optional staleness-weighted buffering of late updates (the stale
    buffer rides in the scan carry, shard-local under a mesh), optional
    battery harvesting, and per-round simulated wall-clock in the logs
    (``RoundLog.t_round``). A disabled config (the default) compiles the
    exact legacy program, so synchronous goldens hold bit-for-bit.

    ``hierarchy``: a ``repro.core.hierarchy.HierarchyConfig`` switches
    the controller to the sampled decide path — clients are k-means
    clustered over channel statistics / device tier at init, each round
    draws a candidate pool ∝ fairness deficit (cluster-stratified), and
    the wrapped controller solves on the gathered ``[K_pool]`` slice.
    Non-candidates carry pinned EMA-decay semantics (see
    ``SampledController``). The sampler base key rides in the scan carry
    (``HierarchyState.key``) and the per-round draw is
    ``fold_in(key, round)`` — (seed, round)-pure, so resume/replay and
    1-device vs N-device runs sample identical pools. A disabled config
    (``pool_frac=1, clusters=1``) does not wrap at all: the compiled
    program is literally the legacy one. Note: under ``run_sweep`` the
    sampler key is shared across seed lanes (it lives in the controller
    state, which all lanes start from), so pools vary per round but not
    per seed — per-seed pool variation needs fresh trainers.

    ``mobility``: a ``repro.core.channel.MobilityConfig`` adds slow
    (seed, round)-pure log-normal pathloss drift (client movement /
    shadowing) to every engine's channel draw. ``None`` — or a config
    with ``sigma_db=0`` — compiles the exact legacy channel stream.

    ``fault_cfg``: a ``repro.core.faults.FaultConfig`` injects
    (seed, round)-pure faults — mid-round crashes with partial-energy
    proration, corrupted payloads, channel-estimate error, and
    open-population churn over the client slots. ``defense``: a
    ``repro.core.faults.DefenseConfig`` routes aggregation through the
    defended aggregator (finite screen, streaming norm clip, optional
    trimmed mean). Either activates the ``RoundLog`` fault-telemetry
    lanes (``n_faulted``/``n_rejected``/``clip_frac``/``fallback``) and
    the whole-round non-finite-aggregate guard. Both disabled (the
    default) compile the exact legacy program — same goldens contract
    as ``async_cfg``.

    ``link_cfg``: a ``repro.core.link.LinkConfig`` models the wireless
    uplink as unreliable — (seed, round, attempt)-pure Rayleigh-outage
    packet errors with bounded HARQ retransmission (real energy and
    airtime per attempt), a Gilbert-Elliott bursty-interference chain
    that raises the effective noise floor while in the burst state, and
    optional outage-aware solver pricing (``price_outage`` folds the
    expected attempt count into the comm-energy term). Activates the
    ``RoundLog`` link lanes (``n_retx``/``n_outage``/``goodput_frac``/
    ``e_retx``). ``None`` — or a config with neither ``outage`` nor a
    bursty chain — compiles the exact legacy program, same goldens
    contract as ``fault_cfg``.

    ``frozen_params``: a frozen model base (e.g. the base of LoRA
    fine-tuning; ``model_params`` then holds only the adapters).
    ``model_loss`` and ``eval_fn`` keep their one params argument and
    are handed ``repro.fl.client.WithFrozen(params, frozen_params)``;
    only ``params`` is differentiated, flattened, sparsified,
    aggregated and checkpointed. The base is an argument of every round
    program (never donated, never copied per client: the client ``vmap``
    broadcasts it; replicated on a clients mesh), so it is not a
    constant of the compiled program. ``run_sweep`` does not take it.

    ``use_pallas_compression``: how the top-k runs, as
    ``compression.batch_block_topk``'s ``use_pallas``: ``None`` (default)
    by backend, the Pallas kernel on a TPU and the jnp bisection
    elsewhere; ``True`` or ``False`` force one. Both give the same bits.
    """

    def __init__(self, *, model_loss, model_params, client_datasets,
                 eval_fn, fl_cfg, fe_cfg, ch_cfg,
                 controller: Union[str, Controller] = "fairenergy",
                 strategy: Optional[str] = None,
                 fixed_k: Optional[int] = None,
                 eco_gamma: float = 0.1, eco_bandwidth: Optional[float] = None,
                 use_pallas_compression: Optional[bool] = None,
                 seed: int = 0,
                 mesh=None, mesh_axis: str = CLIENTS_AXIS,
                 device_profile=None,
                 async_cfg: Optional[AsyncConfig] = None,
                 fault_cfg: Optional[FaultConfig] = None,
                 defense: Optional[DefenseConfig] = None,
                 link_cfg: Optional[LinkConfig] = None,
                 hierarchy: Optional[HierarchyConfig] = None,
                 mobility=None, frozen_params=None):
        if strategy is not None:
            controller = strategy
        self.loss_fn = model_loss
        # held by reference: an argument of every engine call, never
        # donated, copied or baked into a program
        self.frozen_params = frozen_params
        # private copy: the fused engine donates the params buffer, which
        # must never consume the caller's (possibly shared) arrays
        self.params = jax.tree_util.tree_map(jnp.array, model_params)
        self.eval_fn = eval_fn
        self.fl_cfg, self.fe_cfg, self.ch_cfg = fl_cfg, fe_cfg, ch_cfg
        self.n_clients = len(client_datasets)
        self.network = WirelessNetwork(ch_cfg, seed=seed,
                                       device_profile=device_profile,
                                       mobility=mobility)
        # normalized by the network: a disabled (sigma_db=0) config is
        # None here, and every engine below emits the legacy program
        self.mobility = self.network.mobility
        self.device_profile = self.network.device_profile
        self.spec = tree_spec(model_params)
        self.n_params = int(sum(np.prod(s) for s in self.spec.shapes))
        self.s_bits = 32.0 * self.n_params
        self.i_bits = float(self.n_params)            # 1-bit/coeff kept-mask
        self.use_pallas = use_pallas_compression

        # per-round computation energy from the device profile (a round
        # is local_steps minibatches of local_batch samples); None keeps
        # the legacy communication-only objective
        e_cmp = None
        if self.device_profile is not None:
            samples = fl_cfg.local_steps * fl_cfg.local_batch
            e_cmp = tuple(np.asarray(
                comp_energy(self.device_profile, samples), np.float64))
        ctx = ControllerContext(
            n_clients=self.n_clients, b_tot=ch_cfg.bandwidth_total,
            s_bits=self.s_bits, i_bits=self.i_bits, n0=ch_cfg.noise_density,
            fe_cfg=fe_cfg, fixed_k=fixed_k, eco_gamma=eco_gamma,
            eco_bandwidth=eco_bandwidth, e_cmp=e_cmp)
        self.controller = make_controller(controller, ctx)
        self.controller_name = (controller if isinstance(controller, str)
                                else getattr(controller, "name",
                                             type(controller).__name__.lower()))
        # ---- hierarchical control (repro.core.hierarchy) ---------------
        # the wrap is Python-level and only happens when sampling is
        # actually on: a disabled config (pool_frac=1, clusters=1) leaves
        # the controller — and therefore the whole compiled program —
        # literally the legacy one, so the goldens hold bit-for-bit
        if hierarchy is not None and not isinstance(hierarchy, HierarchyConfig):
            raise TypeError(f"hierarchy must be a HierarchyConfig or None, "
                            f"got {type(hierarchy).__name__}")
        self.hierarchy = hierarchy
        if hierarchy is not None and hierarchy.sampling_enabled(self.n_clients):
            self.controller = wrap_controller(
                self.controller, hierarchy, ctx,
                pathloss=self.network.pathloss, power=self.network.power,
                base_key=jax.random.fold_in(jax.random.PRNGKey(seed),
                                            _POOL_STREAM),
                seed=seed)
        self.ctrl_state = self.controller.init(self.n_clients)

        self.seed = seed
        # three independent streams off one per-seed base key (fading uses
        # the base itself, folded by round): distinct stream tags far above
        # any round index, so no stream ever reuses another's bits — which
        # seed+1/seed+2 style bases would do across adjacent sweep seeds
        base = jax.random.PRNGKey(seed)
        self.key = jax.random.fold_in(base, _CTRL_STREAM)       # controller
        self.sample_key = jax.random.fold_in(base, _SAMPLE_STREAM)
        self.harvest_key = jax.random.fold_in(base, _HARVEST_STREAM)
        self.fault_key = jax.random.fold_in(base, _FAULT_STREAM)
        self.link_key = jax.random.fold_in(base, _LINK_STREAM)
        self._client_step_raw = make_batched_client_step(
            model_loss, fl_cfg.lr, jit=False,
            frozen=frozen_params is not None)
        self._client_step = jax.jit(self._client_step_raw)
        self._scan_engine = None
        self._scan_fn_raw = None
        self._sweep_engine = None
        self._cfg_sweep_engine = None
        self._P = jnp.asarray(self.network.power, jnp.float32)
        self.mesh, self.mesh_axis = mesh, mesh_axis
        if mesh is not None:
            # a hierarchy mesh splits the client axis over (clusters,
            # clients); the padded count must divide the product
            caxes = mesh_client_axes(mesh, mesh_axis)
            size = client_shard_count(mesh, mesh_axis)
            self._data = stack_client_datasets(client_datasets,
                                               pad_to_multiple=size)
            self._data = shard_client_data(self._data, mesh, caxes)
        else:
            self._data = stack_client_datasets(client_datasets)
        self.n_padded = self._data.n_clients      # == n_clients when unsharded
        # ghost clients have length 0 => exactly zero aggregation weight
        weights = np.asarray(self._data.lengths, np.float64)
        self.weights = weights / weights.sum()
        # battery charge carried across rounds; inf (unlimited) when the
        # profile has no finite capacities — bit-identical physics to a
        # battery-free run
        if self.device_profile is not None:
            self._battery0 = jnp.asarray(self.device_profile.battery,
                                         jnp.float32)
        else:
            self._battery0 = jnp.full((self.n_clients,), UNLIMITED_J,
                                      jnp.float32)
        self._battery = jnp.array(self._battery0)

        # ---- async round model (repro.core.rounds) ---------------------
        # a disabled config resolves to async_rt=None, and every engine
        # below then builds the exact legacy program (the async carry is
        # the leafless (), the harvest key is dead code)
        self.async_cfg = async_cfg
        self._async_rt = self._resolve_async_runtime(async_cfg, e_cmp, ctx)
        self.deadline_s = (self._async_rt.deadline
                           if self._async_rt is not None else float("inf"))
        if self._async_rt is not None and self._async_rt.staleness:
            self._astate0 = init_async_state(self.n_padded, self.n_params)
        else:
            self._astate0 = ()
        self._astate = jax.tree_util.tree_map(jnp.array, self._astate0)

        # ---- fault injection + defended aggregation (repro.core.faults)
        # a disabled fault config resolves to fault_rt=None and the
        # default "mean" aggregator (with its leafless () carry) emits
        # the exact legacy combine ops — goldens hold bit-for-bit
        if fault_cfg is not None and not isinstance(fault_cfg, FaultConfig):
            raise TypeError(f"fault_cfg must be a FaultConfig or None, got "
                            f"{type(fault_cfg).__name__}")
        if defense is not None and not isinstance(defense, DefenseConfig):
            raise TypeError(f"defense must be a DefenseConfig or None, got "
                            f"{type(defense).__name__}")
        self.fault_cfg = fault_cfg
        self.defense_cfg = defense
        if defense is not None and defense.enabled:
            self.aggregator = make_aggregator("defended", defense)
        else:
            self.aggregator = make_aggregator("mean")
        self._fault_rt = self._resolve_fault_runtime(fault_cfg)
        self._fstate0 = self.aggregator.init()
        self._fstate = jax.tree_util.tree_map(jnp.array, self._fstate0)

        # ---- wireless link reliability (repro.core.link) ----------------
        # a disabled link config resolves to link_rt=None (leafless ()
        # carry, dead link key) and every engine below builds the exact
        # legacy program — same goldens contract as the other subsystems
        if link_cfg is not None and not isinstance(link_cfg, LinkConfig):
            raise TypeError(f"link_cfg must be a LinkConfig or None, got "
                            f"{type(link_cfg).__name__}")
        self.link_cfg = link_cfg
        self._link_rt = self._resolve_link_runtime(link_cfg)
        if self._link_rt is not None and self._link_rt.bursty:
            self._lstate0 = init_link_state(self.n_clients)
        else:
            self._lstate0 = ()
        self._lstate = jax.tree_util.tree_map(jnp.array, self._lstate0)

        # ---- quantized payloads (joint (gamma, bits) grid and/or
        # device-profile default widths) — a (32.0,) grid with no profile
        # widths resolves to quant_rt=None, and every engine below builds
        # the exact legacy full-precision program (goldens contract)
        self._quant_rt = self._resolve_quant_runtime(e_cmp)
        self._calibrated = False
        self.history: list[RoundLog] = []

    def _resolve_async_runtime(self, cfg: Optional[AsyncConfig], e_cmp,
                               ctx: ControllerContext):
        """Materialize the engine-facing ``_AsyncRuntime`` (None when the
        config is absent/disabled): per-client comp time/energy and
        battery caps from the device profile, harvesting rates, and the
        concrete deadline (``deadline_q`` resolved against deterministic
        round-time estimates — pure in the trainer's geometry)."""
        if cfg is None or not cfg.enabled:
            return None
        n = self.n_clients
        if self.device_profile is not None:
            t_cmp = jnp.asarray(
                comp_time(self.device_profile,
                          self.fl_cfg.local_steps * self.fl_cfg.local_batch),
                jnp.float32)
            cap = jnp.asarray(self.device_profile.battery, jnp.float32)
        else:
            t_cmp = jnp.zeros((n,), jnp.float32)
            cap = jnp.full((n,), UNLIMITED_J, jnp.float32)
        e_arr = (jnp.asarray(e_cmp, jnp.float32) if e_cmp is not None
                 else jnp.zeros((n,), jnp.float32))
        deadline = cfg.deadline_s
        if cfg.deadline_q is not None:
            deadline = resolve_deadline(
                cfg.deadline_q, t_cmp=np.asarray(t_cmp),
                P=self.network.power, h=self.network.pathloss,
                b_tot=self.ch_cfg.bandwidth_total, s_bits=self.s_bits,
                i_bits=self.i_bits, n0=self.ch_cfg.noise_density, k=ctx.k)
        rates = None
        if cfg.harvest_j is not None:
            rates = harvest_rates(self.device_profile, n, cfg.harvest_j)
        gamma_floor = getattr(self.fe_cfg, "gamma_min", 0.1) or 0.1
        return _AsyncRuntime(
            deadline=float(deadline), staleness=cfg.staleness,
            staleness_a=float(cfg.staleness_a), t_cmp=t_cmp, e_cmp=e_arr,
            cap=cap, rates=rates, b_tot=float(self.ch_cfg.bandwidth_total),
            gamma_floor=float(gamma_floor), s_bits=self.s_bits,
            i_bits=self.i_bits, n0=float(self.ch_cfg.noise_density))

    def _resolve_fault_runtime(self, cfg: Optional[FaultConfig]):
        """Materialize the engine-facing ``_FaultsRuntime`` (None when
        the config is absent/disabled): per-client computation time and
        energy from the device profile (zeros without one — crash
        proration then charges transmission time only) plus the channel
        scalars the realized-energy re-charge needs."""
        if cfg is None or not cfg.enabled:
            return None
        n = self.n_clients
        if self.device_profile is not None:
            samples = self.fl_cfg.local_steps * self.fl_cfg.local_batch
            t_cmp = jnp.asarray(comp_time(self.device_profile, samples),
                                jnp.float32)
            e_cmp = jnp.asarray(comp_energy(self.device_profile, samples),
                                jnp.float32)
        else:
            t_cmp = jnp.zeros((n,), jnp.float32)
            e_cmp = jnp.zeros((n,), jnp.float32)
        return _FaultsRuntime(
            crash_rate=float(cfg.crash_rate),
            corrupt_rate=float(cfg.corrupt_rate),
            corrupt_mode=str(cfg.corrupt_mode),
            corrupt_scale=float(cfg.corrupt_scale),
            h_err_std=float(cfg.h_err_std),
            churn_dwell=int(cfg.churn_dwell),
            churn_away=float(cfg.churn_away),
            t_cmp=t_cmp, e_cmp=e_cmp,
            b_tot=float(self.ch_cfg.bandwidth_total), s_bits=self.s_bits,
            i_bits=self.i_bits, n0=float(self.ch_cfg.noise_density))

    def _resolve_link_runtime(self, cfg: Optional[LinkConfig]):
        """Materialize the engine-facing ``_LinkRuntime`` (None when the
        config is absent/disabled): the linear fade margin, the
        retransmission budget, the Gilbert-Elliott burst parameters as an
        effective noise rise, and the per-client computation time/energy
        the retransmission accounting charges alongside the airtime."""
        if cfg is None or not cfg.enabled:
            return None
        n = self.n_clients
        if self.device_profile is not None:
            samples = self.fl_cfg.local_steps * self.fl_cfg.local_batch
            t_cmp = jnp.asarray(comp_time(self.device_profile, samples),
                                jnp.float32)
            e_cmp = jnp.asarray(comp_energy(self.device_profile, samples),
                                jnp.float32)
        else:
            t_cmp = jnp.zeros((n,), jnp.float32)
            e_cmp = jnp.zeros((n,), jnp.float32)
        return _LinkRuntime(
            outage=bool(cfg.outage),
            margin=float(10.0 ** (cfg.fade_margin_db / 10.0)),
            max_retx=int(cfg.max_retx), backoff_s=float(cfg.backoff_s),
            bursty=bool(cfg.bursty), burst_p=float(cfg.burst_p),
            burst_q=float(cfg.burst_q),
            noise_rise=1.0 + float(cfg.i_burst_n0),
            observe_burst=bool(cfg.observe_burst),
            price_outage=bool(cfg.price_outage),
            t_cmp=t_cmp, e_cmp=e_cmp,
            b_tot=float(self.ch_cfg.bandwidth_total), s_bits=self.s_bits,
            i_bits=self.i_bits, n0=float(self.ch_cfg.noise_density))

    def _resolve_quant_runtime(self, e_cmp):
        """Materialize the engine-facing ``_QuantRuntime`` (None when
        neither the joint (gamma, bits) grid nor device-profile default
        widths are active): the per-client fallback width, the channel
        scalars the payload-equivalent re-charge and ``e_saved``
        counterfactual need, and the computation energy."""
        n = self.n_clients
        grid = tuple(float(b) for b in
                     (getattr(self.fe_cfg, "bits_grid", None) or (32.0,)))
        active = grid != (32.0,)
        default_bits = None
        prof_bits = (getattr(self.device_profile, "bits", None)
                     if self.device_profile is not None else None)
        if prof_bits is not None:
            pb = np.asarray(prof_bits, np.float32)
            if np.any(pb < 32.0):
                active = True
                default_bits = jnp.asarray(pb, jnp.float32)
        if not active:
            return None
        if default_bits is None:
            default_bits = jnp.full((n,), 32.0, jnp.float32)
        e_arr = (jnp.asarray(e_cmp, jnp.float32) if e_cmp is not None
                 else jnp.zeros((n,), jnp.float32))
        return _QuantRuntime(
            default_bits=default_bits, e_cmp=e_arr,
            b_tot=float(self.ch_cfg.bandwidth_total), s_bits=self.s_bits,
            i_bits=self.i_bits, n0=float(self.ch_cfg.noise_density))

    # back-compat alias (the old attribute name) --------------------------
    @property
    def strategy(self) -> str:
        return self.controller_name

    @property
    def battery(self) -> np.ndarray:
        """[N] current per-client battery charge (J; inf = unlimited)."""
        return np.asarray(self._battery)

    # ------------------------------------------------------------------
    @functools.cached_property
    def _sampler(self):
        return jax.jit(functools.partial(
            sample_round_batches, local_steps=self.fl_cfg.local_steps,
            batch=self.fl_cfg.local_batch, n_real=self.n_clients))

    def _round_batches(self, r: int):
        """Round-r minibatches [N, steps, batch, ...], traced gather."""
        return self._sampler(self._data, self.sample_key, r)

    def _core_kwargs(self):
        return dict(controller=self.controller, spec=self.spec,
                    weights=jnp.asarray(self.weights, jnp.float32),
                    server_lr=self.fl_cfg.server_lr, use_pallas=self.use_pallas)

    def _get_scan_engine(self):
        if self._scan_engine is None:
            scan_fn = make_scan_engine(
                **self._core_kwargs(), client_step=self._client_step_raw,
                eval_fn=self.eval_fn,
                pathloss=jnp.asarray(self.network.pathloss, jnp.float32),
                P=self._P, rayleigh=self.ch_cfg.rayleigh,
                local_steps=self.fl_cfg.local_steps,
                batch=self.fl_cfg.local_batch,
                mesh=self.mesh, mesh_axis=self.mesh_axis,
                n_real=self.n_clients, async_rt=self._async_rt,
                fault_rt=self._fault_rt, aggregator=self.aggregator,
                mobility=self.mobility, link_rt=self._link_rt,
                quant_rt=self._quant_rt)
            self._scan_engine = jax.jit(scan_fn, static_argnames="n_rounds",
                                        donate_argnums=(0, 1, 2, 3, 4, 5))
            self._scan_fn_raw = scan_fn
        return self._scan_engine

    def _get_sweep_engine(self):
        """vmap of the scan program over stacked per-seed keys, jitted and
        cached (XLA caches per (n_rounds, lane-count) under one wrapper)."""
        if self._sweep_engine is None:
            self._get_scan_engine()
            scan_fn = self._scan_fn_raw

            @functools.partial(jax.jit, static_argnames="n_rounds")
            def sweep(params, state, battery, astate, fstate, lstate, data,
                      keys, eval_every, n_rounds: int):
                def one(ks):
                    _, _, _, _, _, _, outs = scan_fn(params, state, battery,
                                                     astate, fstate, lstate,
                                                     data, ks, jnp.int32(0),
                                                     jnp.int32(n_rounds - 1),
                                                     eval_every, n_rounds)
                    return outs
                return jax.vmap(one)(keys)

            self._sweep_engine = sweep
        return self._sweep_engine

    def _get_config_sweep_engine(self):
        """configs (outer vmap) x seeds (inner vmap) of the scan program:
        the whole hyper-parameter sweep is one jitted XLA program. Config
        lanes ride in the stacked controller states (``FEParams`` is a
        traced operand of the solver), so no lane retraces."""
        if self._cfg_sweep_engine is None:
            self._get_scan_engine()
            scan_fn = self._scan_fn_raw

            @functools.partial(jax.jit, static_argnames="n_rounds")
            def sweep(params, states, battery, astate, fstate, lstate, data,
                      keys, eval_every, n_rounds: int):
                def per_cfg(st):
                    def one(ks):
                        _, _, _, _, _, _, outs = scan_fn(
                            params, st, battery, astate, fstate, lstate,
                            data, ks, jnp.int32(0), jnp.int32(n_rounds - 1),
                            eval_every, n_rounds)
                        return outs
                    return jax.vmap(one)(keys)
                return jax.vmap(per_cfg)(states)

            self._cfg_sweep_engine = sweep
        return self._cfg_sweep_engine

    def _stack_config_states(self, configs: dict):
        """Per-lane controller states from a dict of FEParams overrides
        ({"eta": [...], "rho": [...], "b_tot": [...]}, equal-length or
        scalar-broadcast values). Returns (stacked_states, n_lanes,
        echo) — echo is the post-broadcast {field: [n_lanes values]}."""
        from repro.core.fairenergy import FEParams
        base = self.ctrl_state
        rewrap = None
        if hasattr(base, "inner") and hasattr(base, "assign"):
            # sampled decide path: the FEParams live in the wrapped inner
            # state; config lanes replace that and keep the cluster
            # assignment + sampler base key shared across lanes
            outer = base
            base = base.inner
            rewrap = lambda st: outer._replace(inner=st)  # noqa: E731
        if not (hasattr(base, "params") and isinstance(base.params, FEParams)):
            raise ValueError(
                "config sweep needs a controller whose state carries "
                "FEParams (the fairenergy controller); "
                f"got {type(self.controller).__name__}")
        unknown = set(configs) - set(FEParams._fields)
        if unknown:
            raise KeyError(f"unknown FEParams field(s) {sorted(unknown)}; "
                           f"sweepable: {list(FEParams._fields)}")
        vals = {k: np.atleast_1d(np.asarray(v, np.float32))
                for k, v in configs.items()}
        n_lanes = max(v.shape[0] for v in vals.values())
        for k, v in vals.items():
            if v.shape[0] == 1:
                vals[k] = np.broadcast_to(v, (n_lanes,))
            elif v.shape[0] != n_lanes:
                raise ValueError(f"config {k!r} has {v.shape[0]} values, "
                                 f"expected 1 or {n_lanes}")
        # the 1 Hz rate-floor contract (see ControllerContext) must hold
        # on every lane, not just the trainer's own b_tot
        b_lo = vals.get("b_min_frac",
                        np.full(n_lanes, float(base.params.b_min_frac)))
        b_tot = vals.get("b_tot", np.full(n_lanes, float(base.params.b_tot)))
        bad = b_lo * b_tot < 1.0
        if bad.any():
            raise ValueError(
                f"config lane(s) {np.nonzero(bad)[0].tolist()} probe "
                "bandwidth below the 1 Hz rate floor "
                "(b_min_frac * b_tot < 1); raise b_min_frac or b_tot")
        lanes = [base._replace(params=base.params._replace(
            **{k: jnp.float32(v[i]) for k, v in vals.items()}))
            for i in range(n_lanes)]
        if rewrap is not None:
            lanes = [rewrap(st) for st in lanes]
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *lanes)
        echo = {k: np.asarray(v).tolist() for k, v in vals.items()}
        return stacked, n_lanes, echo

    def _invalidate_engines(self):
        self._scan_engine = None
        self._scan_fn_raw = None
        self._sweep_engine = None
        self._cfg_sweep_engine = None

    def _maybe_calibrate(self, r: int):
        """One-shot eta_auto calibration from round-r observations. The
        engines trace the controller's (static) structure, so they are
        rebuilt after calibration — and because the float config rides in
        the controller *state* (``FEParams``), the state is re-inited so
        the calibrated eta reaches the solver."""
        if self._calibrated:
            # one-shot: calibration already ran (or a checkpoint restore
            # brought back a state whose FEParams carry the calibrated
            # eta — re-initing would wipe the restored duals/EMA)
            return
        if not getattr(self.controller, "needs_calibration", False):
            return
        _, u_norms, _ = self._client_step(self.params, self._round_batches(r),
                                          *self._base_arg())
        h = self.network.gains(r)
        # drop ghost-padded rows: calibration medians see only real clients
        self.controller.calibrate(np.asarray(u_norms)[:self.n_clients],
                                  np.asarray(h), self.network.power)
        self.ctrl_state = self.controller.init(self.n_clients)
        self._calibrated = True
        self._invalidate_engines()

    # ------------------------------------------------------------------
    def run_round(self, r: int) -> RoundLog:
        """One round, one host round-trip — the debug path.

        Dispatches the *same* fused step program as ``run_scanned``
        (a chunk of one round), so stepping round-by-round reproduces the
        scanned trajectory — including knife-edge controller decisions
        that a differently-fused program could flip (the two chunk
        lengths still compile separately, so equality is last-ulp-tight
        rather than guaranteed-bitwise).
        """
        self._maybe_calibrate(r)
        engine = self._get_scan_engine()
        with jax.profiler.TraceAnnotation("fl.dispatch"):
            (self.params, self.ctrl_state, self._battery, self._astate,
             self._fstate, self._lstate, outs) = engine(
                self.params, self.ctrl_state, self._battery, self._astate,
                self._fstate, self._lstate, self._data, self._keys(),
                jnp.int32(r), jnp.int32(r), jnp.int32(1), n_rounds=1,
                frozen=self.frozen_params)
        self._append_chunk_logs(r, outs)
        return self.history[-1]

    def run(self, rounds: Optional[int] = None, *, log_every: int = 10,
            verbose: bool = True):
        rounds = rounds or self.fl_cfg.rounds
        for r in range(rounds):
            log = self.run_round(r)
            if verbose and (r % log_every == 0 or r == rounds - 1):
                print(f"[{self.controller_name}] round {r:4d} "
                      f"acc={log.accuracy:.4f} sel={log.n_selected:2d} "
                      f"E={log.total_energy*1e3:.3f} mJ")
        return self.history

    # ------------------------------------------------------- fused engine ----
    def _base_arg(self) -> tuple:
        return () if self.frozen_params is None else (self.frozen_params,)

    def _keys(self):
        return {"fade": self.network.fade_key, "sample": self.sample_key,
                "ctrl": self.key, "harvest": self.harvest_key,
                "fault": self.fault_key, "link": self.link_key}

    def _append_chunk_logs(self, start: int, outs) -> None:
        """Materialize one chunk of stacked scan outputs (single host
        sync) into per-round ``RoundLog``s."""
        with jax.profiler.TraceAnnotation("fl.sync"):
            # waits for the device, then copies the chunk to the host
            host = {k: np.asarray(v) for k, v in outs.items()}
        timed = "t_round" in host
        faulted = "n_faulted" in host
        linked = "n_retx" in host
        quanted = "bits" in host
        with jax.profiler.TraceAnnotation("fl.logs"):
            for i in range(host["x"].shape[0]):
                x = host["x"][i]
                self.history.append(RoundLog(
                    round=start + i, selected=x, gamma=host["gamma"][i],
                    bandwidth=host["bandwidth"][i], energy=host["energy"][i],
                    accuracy=float(host["accuracy"][i]),
                    loss=float(host["loss"][i]), n_selected=int(x.sum()),
                    battery=host["battery"][i] if "battery" in host else None,
                    t_round=float(host["t_round"][i]) if timed else None,
                    made=host["made"][i] if timed else None,
                    n_late=int(host["n_late"][i]) if timed else None,
                    n_stale=int(host["n_stale"][i]) if timed else None,
                    n_faulted=int(host["n_faulted"][i]) if faulted else None,
                    n_rejected=int(host["n_rejected"][i]) if faulted else None,
                    clip_frac=float(host["clip_frac"][i]) if faulted else None,
                    fallback=bool(host["fallback"][i]) if faulted else None,
                    n_retx=int(host["n_retx"][i]) if linked else None,
                    n_outage=int(host["n_outage"][i]) if linked else None,
                    goodput_frac=(float(host["goodput_frac"][i])
                                  if linked else None),
                    e_retx=float(host["e_retx"][i]) if linked else None,
                    bits=host["bits"][i] if quanted else None,
                    e_saved=float(host["e_saved"][i]) if quanted else None))

    def run_scanned(self, rounds: Optional[int] = None, *,
                    chunk: Optional[int] = None, eval_every: int = 1,
                    verbose: bool = True, start_round: int = 0,
                    ckpt_dir: Optional[str] = None, ckpt_every: int = 1):
        """Run ``rounds`` FL rounds through the fused ``lax.scan`` engine.

        ``chunk`` bounds the rounds per compiled program (default: all
        rounds as one scan); ``eval_every`` strides the in-scan accuracy
        evaluation (skipped rounds log ``accuracy=NaN``; the final round
        is always evaluated). Appends to ``history`` exactly like
        ``run`` and returns it.

        Like ``run``, every call restarts at round 0 — and because all
        randomness is pure in (seed, round), a second call replays the
        identical batches and channel draws. Use fresh trainers (or
        ``run_sweep`` seeds) for independent repetitions.

        ``start_round`` resumes mid-trajectory — the carry must already
        hold the state of that round (i.e. after ``restore_checkpoint``);
        randomness being pure in (seed, round), the remaining rounds
        replay bit-for-bit. With ``ckpt_dir``, the full scan carry
        (params, controller state, batteries, async buffer) is saved via
        ``repro.checkpoint`` every ``ckpt_every`` chunks and after the
        final round.
        """
        rounds = rounds or self.fl_cfg.rounds
        chunk = min(chunk or rounds, rounds)
        if eval_every < 1:
            raise ValueError(f"eval_every must be >= 1, got {eval_every} "
                             "(it strides the in-scan eval; use a large "
                             "value to evaluate only the final round)")
        if not 0 <= start_round < rounds:
            raise ValueError(f"start_round {start_round} outside "
                             f"[0, {rounds})")
        if ckpt_every < 1:
            raise ValueError(f"ckpt_every must be >= 1, got {ckpt_every}")
        self._maybe_calibrate(start_round)
        engine = self._get_scan_engine()
        keys = self._keys()
        for ci, s in enumerate(range(start_round, rounds, chunk)):
            n = min(chunk, rounds - s)
            with jax.profiler.TraceAnnotation("fl.dispatch"):
                (self.params, self.ctrl_state, self._battery, self._astate,
                 self._fstate, self._lstate, outs) = engine(
                    self.params, self.ctrl_state, self._battery,
                    self._astate, self._fstate, self._lstate, self._data,
                    keys, jnp.int32(s), jnp.int32(rounds - 1),
                    jnp.int32(eval_every), n_rounds=n,
                    frozen=self.frozen_params)
            self._append_chunk_logs(s, outs)
            if ckpt_dir is not None and ((ci + 1) % ckpt_every == 0
                                         or s + n >= rounds):
                self.save_checkpoint(ckpt_dir, s + n)
            if verbose:
                lg = self.history[-1]
                print(f"[{self.controller_name}] rounds {s:4d}..{s + n - 1:4d} "
                      f"acc={lg.accuracy:.4f} sel={lg.n_selected:2d} "
                      f"E={lg.total_energy*1e3:.3f} mJ")
        return self.history

    def lower_scanned(self, rounds: int, *, eval_every: int = 1):
        """The fused engine's program for one ``rounds``-round chunk from
        round 0 at the current carry, lowered but not run: ``.compile()``
        gives its compile time, ``.as_text()`` the program (e.g. to find
        the Pallas kernels, ``tpu_custom_call``) and its cost and memory
        analyses. Runs the one-shot calibration first, as ``run_scanned``
        would, so the program is the one that ``run_scanned(rounds)``
        executes. Donates nothing."""
        self._maybe_calibrate(0)
        return self._get_scan_engine().lower(
            self.params, self.ctrl_state, self._battery, self._astate,
            self._fstate, self._lstate, self._data, self._keys(),
            jnp.int32(0), jnp.int32(rounds - 1), jnp.int32(eval_every),
            n_rounds=rounds, frozen=self.frozen_params)

    # ------------------------------------------------------- checkpointing ----
    def _carry_tree(self) -> dict:
        """The full scan carry as one pytree (what a checkpoint holds):
        params, controller state (duals / fairness EMA / FEParams),
        batteries, the async stale buffer, the defended-aggregation
        state (streaming clip quantile), and the link burst state
        (Gilbert-Elliott chain)."""
        return {"params": self.params, "ctrl_state": self.ctrl_state,
                "battery": self._battery, "astate": self._astate,
                "fstate": self._fstate, "lstate": self._lstate}

    def save_checkpoint(self, directory: str, next_round: int) -> str:
        """Persist the carry after round ``next_round - 1``; resuming at
        ``start_round=next_round`` continues the trajectory bit-for-bit
        (pinned by ``tests/test_async_rounds.py``)."""
        return _ckpt.save_checkpoint(
            directory, next_round, self._carry_tree(),
            metadata={"next_round": int(next_round), "seed": int(self.seed),
                      "controller": self.controller_name,
                      "n_history": len(self.history)})

    def restore_checkpoint(self, path: str) -> int:
        """Load a checkpoint into the live carry and return the round to
        resume from (``run_scanned(start_round=...)``). The restored
        controller state already carries any calibrated ``FEParams``, so
        calibration is marked done — re-initing would wipe the restored
        duals/EMA."""
        tree = _ckpt.restore_checkpoint(path, self._carry_tree())
        meta = _ckpt.load_metadata(path)
        (self.params, self.ctrl_state, self._battery, self._astate,
         self._fstate, self._lstate) = (
            jax.tree_util.tree_map(jnp.asarray, tree["params"]),
            jax.tree_util.tree_map(jnp.asarray, tree["ctrl_state"]),
            jnp.asarray(tree["battery"]),
            jax.tree_util.tree_map(jnp.asarray, tree["astate"]),
            jax.tree_util.tree_map(jnp.asarray, tree["fstate"]),
            jax.tree_util.tree_map(jnp.asarray, tree["lstate"]))
        self._calibrated = True
        return int(meta["next_round"])

    @staticmethod
    def _seed_keys(base):
        """Per-seed sweep key streams, the single source of the stream
        protocol (fade uses the base itself, folded by round; see the
        stream-tag note in __init__)."""
        return {"fade": base,
                "ctrl": jax.random.fold_in(base, _CTRL_STREAM),
                "sample": jax.random.fold_in(base, _SAMPLE_STREAM),
                "harvest": jax.random.fold_in(base, _HARVEST_STREAM),
                "fault": jax.random.fold_in(base, _FAULT_STREAM),
                "link": jax.random.fold_in(base, _LINK_STREAM)}

    @classmethod
    def _stacked_seed_keys(cls, bases):
        """[S]-stacked key-lane pytree for the vmapped sweep engines."""
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                      *[cls._seed_keys(b) for b in bases])

    def run_sweep(self, seeds, rounds: Optional[int] = None, *,
                  eval_every: int = 1, configs: Optional[dict] = None) -> dict:
        """vmap the scanned engine over per-seed key sets — and, with
        ``configs``, over stacked hyper-parameter lanes.

        Every lane starts from the trainer's *current* params and
        controller state (the model init on a fresh trainer — sweep
        before training for independent-run error bars) and shares the
        client shards and geometry, but draws independent fading, batch,
        and controller randomness — the multi-seed error-bar protocol at
        roughly single-run wall-clock.
        Returns stacked numpy arrays: ``accuracy``/``loss`` [S, R],
        ``x``/``gamma``/``bandwidth``/``energy`` [S, R, N]. With
        ``eta_auto`` controllers, eta is calibrated once from this
        trainer's own round-0 draw and shared across seeds (it seeds the
        controller state's FEParams). ``history``/``params`` are left
        untouched.

        ``configs`` maps ``FEParams`` field names (``eta``, ``rho``,
        ``b_tot``, ``pi_min``, ...) to equal-length value lists — C
        config lanes riding in the stacked controller states, so seeds x
        configs run as ONE jitted program (no retraces: the whole float
        config is a traced operand of the solver). Output arrays gain a
        leading config axis ([C, S, R, ...]) and the returned dict echoes
        the lanes under ``"configs"``. Requires a controller whose state
        carries ``FEParams`` (fairenergy).
        """
        rounds = rounds or self.fl_cfg.rounds
        if eval_every < 1:
            raise ValueError(f"eval_every must be >= 1, got {eval_every}")
        if self.frozen_params is not None:
            raise NotImplementedError(
                "run_sweep with frozen_params: the sweep engines do not "
                "take a frozen base; run one trainer per seed")
        self._maybe_calibrate(0)
        bases = [jax.random.PRNGKey(int(s)) for s in seeds]
        if configs is not None:
            return self._run_config_sweep(bases, rounds, eval_every, configs)
        if self.mesh is not None:
            # sharded engine: shard_map doesn't vmap over the key lanes, so
            # run the (already sharded, scanned) program once per seed —
            # lanes stack on host. Fresh copies per lane: the engine
            # donates its params/state arguments.
            engine = self._get_scan_engine()
            lanes = []
            for b in bases:
                keys = self._seed_keys(b)
                p = jax.tree_util.tree_map(jnp.array, self.params)
                st = jax.tree_util.tree_map(jnp.array, self.ctrl_state)
                bt = jnp.array(self._battery0)
                ast = jax.tree_util.tree_map(jnp.array, self._astate0)
                fst = jax.tree_util.tree_map(jnp.array, self._fstate0)
                lst = jax.tree_util.tree_map(jnp.array, self._lstate0)
                _, _, _, _, _, _, outs = engine(p, st, bt, ast, fst, lst,
                                                self._data, keys, jnp.int32(0),
                                                jnp.int32(rounds - 1),
                                                jnp.int32(eval_every),
                                                n_rounds=rounds)
                lanes.append({k: np.asarray(v) for k, v in outs.items()})
            return {k: np.stack([ln[k] for ln in lanes]) for k in lanes[0]}
        keys = self._stacked_seed_keys(bases)
        outs = self._get_sweep_engine()(
            self.params, self.ctrl_state, jnp.array(self._battery0),
            jax.tree_util.tree_map(jnp.array, self._astate0),
            jax.tree_util.tree_map(jnp.array, self._fstate0),
            jax.tree_util.tree_map(jnp.array, self._lstate0),
            self._data, keys, jnp.int32(eval_every), n_rounds=rounds)
        return {k: np.asarray(v) for k, v in outs.items()}

    def _run_config_sweep(self, bases, rounds: int, eval_every: int,
                          configs: dict) -> dict:
        """seeds x config lanes. Single-device: one jitted program
        (configs and seeds both vmapped). Sharded: shard_map does not
        vmap over lanes, so (config, seed) pairs run sequentially."""
        # echo comes back post-broadcast: every key has exactly n_lanes
        # values, matching the result arrays' leading config axis
        states, n_lanes, echo = self._stack_config_states(configs)
        if self.mesh is not None:
            engine = self._get_scan_engine()
            lanes = []
            for c in range(n_lanes):
                st_c = jax.tree_util.tree_map(lambda x: x[c], states)
                per_seed = []
                for b in bases:
                    keys = self._seed_keys(b)
                    p = jax.tree_util.tree_map(jnp.array, self.params)
                    st = jax.tree_util.tree_map(jnp.array, st_c)
                    bt = jnp.array(self._battery0)
                    ast = jax.tree_util.tree_map(jnp.array, self._astate0)
                    fst = jax.tree_util.tree_map(jnp.array, self._fstate0)
                    lst = jax.tree_util.tree_map(jnp.array, self._lstate0)
                    _, _, _, _, _, _, outs = engine(p, st, bt, ast, fst, lst,
                                                    self._data, keys,
                                                    jnp.int32(0),
                                                    jnp.int32(rounds - 1),
                                                    jnp.int32(eval_every),
                                                    n_rounds=rounds)
                    per_seed.append({k: np.asarray(v) for k, v in outs.items()})
                lanes.append({k: np.stack([s[k] for s in per_seed])
                              for k in per_seed[0]})
            res = {k: np.stack([ln[k] for ln in lanes]) for k in lanes[0]}
            res["configs"] = echo
            return res
        keys = self._stacked_seed_keys(bases)
        outs = self._get_config_sweep_engine()(
            self.params, states, jnp.array(self._battery0),
            jax.tree_util.tree_map(jnp.array, self._astate0),
            jax.tree_util.tree_map(jnp.array, self._fstate0),
            jax.tree_util.tree_map(jnp.array, self._lstate0),
            self._data, keys, jnp.int32(eval_every), n_rounds=rounds)
        res = {k: np.asarray(v) for k, v in outs.items()}
        res["configs"] = echo
        return res

    # -------------------------------------------------------- statistics ----
    def participation_counts(self) -> np.ndarray:
        return np.sum([lg.selected for lg in self.history], axis=0)

    def energy_per_round(self) -> np.ndarray:
        return np.array([lg.total_energy for lg in self.history])

    def accuracy_curve(self) -> np.ndarray:
        return np.array([lg.accuracy for lg in self.history])

    def energy_to_accuracy(self, target: float) -> float | None:
        cum = 0.0
        for lg in self.history:
            cum += lg.total_energy
            if lg.accuracy >= target:
                return cum
        return None

    def simulated_time(self) -> float:
        """Cumulative simulated wall-clock (s) across the logged rounds
        (``RoundLog.t_round``); untimed rounds count zero."""
        return float(sum(lg.t_round or 0.0 for lg in self.history))

    def wallclock_to_accuracy(self, target: float) -> float | None:
        """Simulated seconds until accuracy first reaches ``target`` —
        the headline metric of the async-round benchmarks. None if the
        target is never reached (or the run is untimed)."""
        cum = 0.0
        timed = False
        for lg in self.history:
            cum += lg.t_round or 0.0
            timed = timed or lg.t_round is not None
            if timed and lg.accuracy >= target:
                return cum
        return None

    def mean_gamma_selected(self) -> float:
        vals = [g for lg in self.history for g in lg.gamma[lg.selected]]
        return float(np.mean(vals)) if vals else 1.0

    def min_bandwidth_selected(self) -> float:
        vals = [b for lg in self.history for b in lg.bandwidth[lg.selected] if b > 0]
        return float(np.min(vals)) if vals else self.ch_cfg.bandwidth_total
