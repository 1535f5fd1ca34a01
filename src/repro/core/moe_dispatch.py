"""Cluster-routed MoE expert dispatch — token->expert routing run as the
paper's skew join through the instrumented exchange.

The dense ``models/moe.py`` layer treats dispatch as a single-program
slot-major transpose; this module treats it as what the paper says it
is — a skewed partition + exchange over t machines:

  Round 1   route tokens (top-k over router logits), all_gather the tiny
            per-expert and per-slot histograms (StatJoin's statistics
            collection), derive each assignment's globally unique
            position within its slot.
  Round 2   the dispatch exchange: every (slot, pos, x) row travels to
            the machine owning its slot through the flat routed-row
            exchange (``exchange_routed_rows`` — the same packed-tile
            ``lax.all_to_all`` the sort shuffles use), and lands in a
            (slots_per_machine, capacity, d) buffer.  Per-slot capacity
            comes from ``CapacityPolicy.moe_dispatch()`` — Theorem 6's
            deterministic 2*T*K/n_slots bound — with the shared
            retry-on-overflow loop, NOT a hand-tuned factor.
  Round 3   expert FFN over the local slots, then the return exchange:
            ``lax.all_to_all`` applied twice is an involution, so each
            source reconstructs its tokens' outputs from the landed
            tile layout it packed in Round 2.

Slot s is owned by machine ``s % t`` (round-robin), so a hot expert's
replica slots spread across machines — the planner's greedy
``plan_slots`` split lands its rectangles on distinct machines exactly
like StatJoin's result-to-machine map.

Every collective goes through the CollectiveTape, so the resulting
AlphaKReport's per-machine workload, per-slot and per-expert counts are
measured inside the jitted program (bitwise against a host recount).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.cluster.capacity import CapacityPolicy, run_with_capacity
from repro.cluster.collectives import CollectiveTape
from repro.cluster.substrate import Substrate

from .exchange import PAD, exchange_routed_rows, return_routed_rows

__all__ = ["moe_dispatch_shard", "cluster_moe_dispatch", "MoeDispatchResult"]


class MoeDispatchResult(NamedTuple):
    y: jnp.ndarray              # (m, d) combined expert outputs, token order
    dropped: jnp.ndarray        # global dropped assignments (scalar, psum'd)
    kept: jnp.ndarray           # assignments processed on this machine
    slot_counts: jnp.ndarray    # (NS,) global per-slot assignment counts
    expert_counts: jnp.ndarray  # (E,) global per-expert assignment counts


def moe_dispatch_shard(x_local: jnp.ndarray, router: jnp.ndarray,
                       w_gate: jnp.ndarray, w_up: jnp.ndarray,
                       w_down: jnp.ndarray, slot2expert: jnp.ndarray,
                       slot_table: jnp.ndarray, replicas: jnp.ndarray, *,
                       axis_name, t: int, num_experts: int, top_k: int,
                       extra_slots: int, capacity_slot: int, cap_pair: int,
                       act: str = "swiglu",
                       tape: Optional[CollectiveTape] = None
                       ) -> MoeDispatchResult:
    """Per-machine cluster MoE dispatch body.  x_local: (m, d) tokens.

    ``slot2expert``/``slot_table``/``replicas`` are the (host-planned)
    StatJoin slot plan from :func:`repro.models.moe.plan_slots` — in the
    cluster path its input counts come from the planner's heavy-hitter
    sketch, not an in-program histogram, so planning costs one sketch
    pass instead of a per-step replan.  ``capacity_slot`` bounds tokens
    per slot (Theorem 6 via CapacityPolicy); ``cap_pair`` bounds the
    per-(src, dst) exchange tile like the sort shuffles' flat capacity.
    """
    if tape is None:
        tape = CollectiveTape()
    m, d = x_local.shape
    e, k = num_experts, top_k
    n_slots = e + extra_slots
    s_local = -(-n_slots // t)          # slots owned per machine (round-robin)
    me = lax.axis_index(axis_name)
    # log-depth prefix sum — same rationale as models/moe.py (XLA:CPU
    # lowers cumsum to a quadratic reduce-window on long token axes)
    prefix = functools.partial(lax.associative_scan, jnp.add, axis=0)

    # ---- Round 1: route + global position bookkeeping ---------------------
    with tape.phase("round1 route stats"):
        logits = jnp.einsum("md,de->me", x_local.astype(jnp.float32), router)
        gate_vals, ids = lax.top_k(logits, k)              # (m, K)
        gates = jax.nn.softmax(gate_vals, axis=-1).reshape(-1)
        flat_ids = ids.reshape(-1)                         # (m*K,) token-major
        onehot_e = jax.nn.one_hot(flat_ids, e, dtype=jnp.int32)
        counts_e = jnp.sum(onehot_e, axis=0)               # (E,) local
        counts_all_e = tape.all_gather(counts_e, axis_name, count=e)  # (t, E)
        tot_e = jnp.sum(counts_all_e, axis=0)
        off_e = (jnp.cumsum(counts_all_e, axis=0) - counts_all_e)[me]
        pos_in_e = (jnp.take_along_axis(prefix(onehot_e) - onehot_e,
                                        flat_ids[:, None], axis=1)[:, 0]
                    + off_e[flat_ids])                     # global, per expert
        rho = pos_in_e % replicas[flat_ids]                # StatJoin even split
        slot = jnp.take_along_axis(slot_table[flat_ids],
                                   jnp.clip(rho, 0, extra_slots)[:, None],
                                   axis=1)[:, 0]
        onehot_s = jax.nn.one_hot(slot, n_slots, dtype=jnp.int32)
        counts_s = jnp.sum(onehot_s, axis=0)
        counts_all_s = tape.all_gather(counts_s, axis_name, count=n_slots)
        tot_s = jnp.sum(counts_all_s, axis=0)
        off_s = (jnp.cumsum(counts_all_s, axis=0) - counts_all_s)[me]
        pos = (jnp.take_along_axis(prefix(onehot_s) - onehot_s,
                                   slot[:, None], axis=1)[:, 0]
               + off_s[slot])                              # global, per slot

    # ---- Round 2: the dispatch exchange -----------------------------------
    with tape.phase("round2 dispatch"):
        owner = (slot % t).astype(jnp.int32)
        payload = jnp.concatenate(
            [slot.astype(jnp.float32)[:, None],
             pos.astype(jnp.float32)[:, None],
             jnp.repeat(x_local.astype(jnp.float32), k, axis=0)], axis=1)
        routed = exchange_routed_rows(owner, payload, axis_name=axis_name,
                                      t=t, cap_pair=cap_pair, tape=tape)
        valid = routed.recv_keys < jnp.asarray(PAD, routed.recv_keys.dtype)
        slot_r = routed.recv_payload[..., 0].astype(jnp.int32)
        pos_r = routed.recv_payload[..., 1].astype(jnp.int32)
        keep_r = valid & (pos_r < capacity_slot)
        # slot s lives at local index s // t on machine s % t
        tgt = jnp.where(keep_r, (slot_r // t) * capacity_slot + pos_r,
                        s_local * capacity_slot)           # trash row last
        rows = routed.recv_payload[..., 2:]                # (t, cap_pair, d)
        buf = jnp.zeros((s_local * capacity_slot + 1, d), rows.dtype)
        buf = buf.at[tgt.reshape(-1)].add(rows.reshape(-1, d))[:-1]
        buf = buf.reshape(s_local, capacity_slot, d)
        recv_drop = jnp.sum(valid & ~keep_r)
        dropped = tape.psum(routed.local_drop + recv_drop,
                            axis_name).astype(jnp.int32)
        kept = jnp.sum(keep_r).astype(jnp.int32)

    # ---- Round 3: expert FFN + return exchange ----------------------------
    with tape.phase("round3 experts"):
        my_slots = jnp.arange(s_local, dtype=jnp.int32) * t + me
        exp_ids = slot2expert[jnp.clip(my_slots, 0, n_slots - 1)]
        wg = w_gate[exp_ids]                               # (S, d, ff)
        wu = w_up[exp_ids]
        wd = w_down[exp_ids]
        g = jnp.einsum("scd,sdf->scf", buf, wg)
        u = jnp.einsum("scd,sdf->scf", buf, wu)
        h = (jax.nn.gelu(g.astype(jnp.float32)) if act == "geglu"
             else jax.nn.silu(g.astype(jnp.float32))).astype(buf.dtype) * u
        out_buf = jnp.einsum("scf,sfd->scd", h, wd)
        out_flat = jnp.concatenate([out_buf.reshape(-1, d),
                                    jnp.zeros((1, d), out_buf.dtype)])
        back = out_flat[tgt]                               # (t, cap_pair, d)
        valid_per_src = jnp.sum(valid, axis=1)             # (t,)
        sent_back = jnp.sum(valid_per_src) - valid_per_src[me]
        # rows I sent that actually landed (per-pair capacity clip) come
        # back to me — the tape's received count for the return hop
        recv_back = jnp.sum(jnp.minimum(routed.lens, cap_pair))
        y_rows = return_routed_rows(back, routed, axis_name=axis_name,
                                    tape=tape, sent=sent_back,
                                    received=recv_back)    # (m*K, d)
        keep_src = pos < capacity_slot
        w = gates * keep_src.astype(gates.dtype)
        y = jnp.sum((y_rows * w[:, None]).reshape(m, k, d), axis=1)
    return MoeDispatchResult(y.astype(x_local.dtype), dropped, kept,
                             tot_s, tot_e)


# ---------------------------------------------------------------------------
# Host-level wrapper: plan slots, run on a substrate, capacity retry.
# ---------------------------------------------------------------------------

def cluster_moe_dispatch(params, x: jnp.ndarray, cfg, *, t_machines: int,
                         counts=None, substrate: Optional[Substrate] = None,
                         policy: Optional[CapacityPolicy] = None,
                         act: str = "swiglu"):
    """Run one MoE layer with cluster-routed dispatch.

    x: (..., d) tokens; the flattened token count must divide evenly
    over ``t_machines``.  ``counts``: (E,) estimated per-expert token
    counts driving the greedy ``plan_slots`` replica allocation —
    normally the planner's CountMin/heavy-hitter estimate
    (``repro.planner.expert_counts_estimate``); ``None`` plans uniform
    replicas.  ``policy`` defaults to ``CapacityPolicy.moe_dispatch()``
    (Theorem 6); per-slot and per-pair capacities scale together through
    the retry loop.  Returns ``(y, report)`` with y shaped like x and an
    AlphaKReport carrying ``slot_workload`` / ``expert_workload`` /
    ``capacity`` / ``cap_factor`` / ``capacity_attempts``.
    """
    from repro.cluster.substrate import default_pool
    from repro.models.moe import plan_slots

    orig_shape = x.shape
    d = int(x.shape[-1])
    xt = jnp.reshape(x, (-1, d))
    tt = int(xt.shape[0])
    t = int(t_machines)
    if tt % t:
        raise ValueError(f"cluster moe_dispatch needs the token count {tt} "
                         f"to divide over t_machines={t}")
    m = tt // t
    e, k = int(cfg.num_experts), int(cfg.top_k)
    n_slots = e + int(cfg.extra_slots)
    if counts is None:
        counts = np.full((e,), max(1, tt * k // e), dtype=np.int64)
    s2e, rep, table = plan_slots(
        jnp.asarray(np.asarray(counts).astype(np.int32)), e,
        int(cfg.extra_slots))
    if substrate is None or (callable(substrate)
                             and not isinstance(substrate, Substrate)):
        provider = substrate if substrate is not None else default_pool()
        substrate = provider(t)
    if substrate.t != t or len(substrate.axes) != 1:
        raise ValueError(f"substrate axes {substrate.axes} do not match "
                         f"t_machines={t} (cluster dispatch is flat)")
    if policy is None:
        policy = CapacityPolicy.moe_dispatch()

    def tile(a):
        a = jnp.asarray(a)
        return jnp.broadcast_to(a, (t,) + a.shape)

    xr = xt.reshape(t, m, d)
    args = (xr, tile(params["router"]), tile(params["w_gate"]),
            tile(params["w_up"]), tile(params["w_down"]),
            tile(s2e), tile(table), tile(rep))

    def attempt(factor):
        capacity_slot = max(1, math.ceil(factor * tt * k / n_slots))
        cap_pair = max(1, math.ceil(factor * m * k / t))
        static = dict(axis_name=substrate.axis_name, t=t, num_experts=e,
                      top_k=k, extra_slots=int(cfg.extra_slots),
                      capacity_slot=capacity_slot, cap_pair=cap_pair,
                      act=act)
        res, tape = substrate.run(
            functools.partial(moe_dispatch_shard, **static), *args)
        return ((res, tape, capacity_slot),
                int(np.asarray(res.dropped).reshape(-1)[0]))

    (res, tape, capacity_slot), factor, attempts = run_with_capacity(
        attempt, policy)

    kept = np.asarray(res.kept).reshape(-1)
    report = tape.report(algorithm="moe[cluster]", t=t, n_in=tt * k,
                         n_out=tt * k, workload=kept)
    report.dispatch_mode = "cluster"
    report.slot_workload = np.asarray(res.slot_counts).reshape(t, -1)[0]
    report.expert_workload = np.asarray(res.expert_counts).reshape(t, -1)[0]
    report.k_slot = float(report.slot_workload.max()
                          / max(1.0, tt * k / n_slots))
    report.k_expert = float(report.expert_workload.max()
                            / max(1.0, tt * k / e))
    report.capacity = int(capacity_slot)
    report.cap_factor = factor
    report.capacity_attempts = attempts
    report.total_dropped = 0
    report.slot2expert = np.asarray(s2e)
    report.slot_replicas = np.asarray(rep)
    y = jnp.reshape(jnp.asarray(res.y), orig_shape)
    return y, report
