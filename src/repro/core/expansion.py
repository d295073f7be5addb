"""Phase 1 — growth-zone candidate expansion (vectorized reference).

The paper's ``try_to_transit`` loop, re-thought for SIMD/TPU execution:

* Definition 3 makes the successor of a motif unique ("no earlier valid
  transition"), so processes never fork.  Candidate *i* is therefore exactly
  the process seeded by edge *i* — a static, allocator-free table.
* Candidates are lanes of a ``[Z, E]`` table, one per zone slot.  Trip
  ``d`` of the loop offers every lane the edge ``d`` slots after its seed
  (extension test + relabeling encode, one dense vector step over the
  table), and the loop stops at the Lemma-4.1 horizon, past which no edge
  can change an output (:func:`horizon_trips`).

State (structure-of-arrays over candidates; shown per zone, the scan
carries a leading zone axis):
  ``length``  int32[C]  edges absorbed so far (0 = not yet seeded)
  ``last_t``  int32[C]  timestamp of the newest edge
  ``done``    bool[C]   timed out (frozen forever)
  ``n_nodes`` int32[C]  node-table population
  ``nodes``   int32[C,K] first-occurrence node table, K = l_max + 1, -1 = empty
  ``code``    int32[C,L] multi-limb relabeling code (see core.encoding)
  ``ts``      int32[C,l_max] per-step absorption timestamps (``with_ts``
              only; ``ts[:, k]`` is the timestamp of the k-th absorbed
              edge, ``ts[:, 0]`` the seed time).  The config-lattice
              co-mining path derives every smaller ``(delta, l_max)``
              config's counts from one dominating sweep by prefix-
              truncating candidates on these timestamps
              (:func:`derive_lengths`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import encoding


class ZoneState(NamedTuple):
    length: jax.Array
    last_t: jax.Array
    done: jax.Array
    n_nodes: jax.Array
    nodes: jax.Array
    code: jax.Array
    ts: jax.Array | None = None


class ZoneResult(NamedTuple):
    """Final per-candidate codes of one zone (candidate i = seed edge i)."""

    code: jax.Array     # int32[C, L]
    length: jax.Array   # int32[C] (0 for padding slots)
    ts: jax.Array | None = None   # int32[C, l_max] absorption timestamps
    #: int32 scalar: trips the scan ran (:func:`horizon_trips`); None for
    #: a backend that does not report one
    trips: jax.Array | None = None


def seed_state(u, v, t, valid, *, l_max: int,
               with_ts: bool = False) -> ZoneState:
    """Trip 0: every valid lane seeded from its own edge (slot == lane).

    Invalid lanes stay unseeded (length 0) and no later edge touches them.
    """
    k_iota = jnp.arange(l_max + 1, dtype=jnp.int32)
    same_uv = u == v
    two = jnp.where(same_uv, 1, 2)
    nodes = jnp.where(valid[..., None] & (k_iota == 0), u[..., None], -1)
    nodes = jnp.where(valid[..., None] & (k_iota == 1) & ~same_uv[..., None],
                      v[..., None], nodes)
    zero = jnp.zeros(u.shape, jnp.int32)
    code = encoding.append_digit(encoding.empty_code(u.shape, l_max), zero,
                                 valid.astype(jnp.int32))
    code = encoding.append_digit(code, zero + 1, jnp.where(valid, two, 0))
    ts = None
    if with_ts:
        step_iota = jnp.arange(l_max, dtype=jnp.int32)
        ts = jnp.where(valid[..., None] & (step_iota == 0), t[..., None], 0)
    return ZoneState(
        length=valid.astype(jnp.int32),
        last_t=jnp.where(valid, t, 0),
        done=jnp.zeros(u.shape, bool),
        n_nodes=jnp.where(valid, two, 0),
        nodes=nodes,
        code=code,
        ts=ts,
    )


def step(state: ZoneState, edge, *, delta: int, l_max: int) -> ZoneState:
    """Offer every lane one edge of its own: time-outs, then extensions.

    ``edge`` is ``(u, v, t, valid)``, each shaped like ``state.length``
    (one edge per lane); an invalid edge changes nothing.
    """
    u, v, t, valid = edge

    active = (state.length > 0) & ~state.done
    gap_ok = (t > state.last_t) & (t - state.last_t <= delta)
    timed_out = active & (t - state.last_t > delta) & valid
    done = state.done | timed_out

    u_hit = state.nodes == u[..., None]
    v_hit = state.nodes == v[..., None]
    u_in = u_hit.any(axis=-1)
    v_in = v_hit.any(axis=-1)
    extend = (
        active & ~timed_out & gap_ok & (state.length < l_max)
        & (u_in | v_in) & valid
    )

    # first-occurrence relabeling (Phase 3 encoding, fused into the sweep)
    k_iota = jnp.arange(state.nodes.shape[-1], dtype=jnp.int32)
    label_u = jnp.where(u_in, jnp.argmax(u_hit, axis=-1), state.n_nodes)
    nn1 = state.n_nodes + (~u_in).astype(jnp.int32)
    same_uv = u == v
    label_v = jnp.where(
        same_uv, label_u, jnp.where(v_in, jnp.argmax(v_hit, axis=-1), nn1)
    )
    nn2 = jnp.where(same_uv, nn1, nn1 + (~v_in).astype(jnp.int32))

    put_u = extend & ~u_in
    put_v = extend & ~v_in & ~same_uv
    nodes = jnp.where(
        (put_u[..., None] & (k_iota == state.n_nodes[..., None])),
        u[..., None], state.nodes
    )
    nodes = jnp.where(
        (put_v[..., None] & (k_iota == nn1[..., None])), v[..., None], nodes
    )

    pos = 2 * state.length
    code = encoding.append_digit(
        state.code, pos, jnp.where(extend, label_u + 1, 0)
    )
    code = encoding.append_digit(
        code, pos + 1, jnp.where(extend, label_v + 1, 0)
    )

    ts = state.ts
    if ts is not None:
        # this edge's timestamp at the step it was absorbed: slot
        # state.length (pre-increment)
        step_iota = jnp.arange(ts.shape[-1], dtype=jnp.int32)
        ts = jnp.where(
            extend[..., None] & (step_iota == state.length[..., None]),
            t[..., None], ts)

    return ZoneState(
        length=state.length + extend.astype(jnp.int32),
        last_t=jnp.where(extend, t, state.last_t),
        done=done,
        n_nodes=jnp.where(extend, nn2, state.n_nodes),
        nodes=nodes, code=code, ts=ts)


def horizon_trips(t, valid, *, span: int):
    """Trips a scan of a ``[Z, E]`` zone batch needs: ``1 + max(h_i - i)``.

    ``h_i`` is the last slot of lane ``i``'s row whose edge, or any later
    one, can still lie within ``span = l_max * delta`` of the lane's seed
    ``t_i``: the last slot whose suffix minimum of valid timestamps is at
    most ``t_i + span``.  Every edge past ``h_i`` is later than
    ``t_i + span``, and after ``k`` extensions ``last_t <= t_i +
    k * delta`` with ``k < l_max`` for any further one, so such an edge can
    only time the lane out, which no output reads.  The maximum is over
    valid lanes (0 for a batch with none).

    The suffix minimum never decreases, so ``h_i >= i + d`` exactly when
    slot ``i + d`` of it is within the lane's bound, even on a row that is
    not time-sorted; that test is monotone in ``d``, so a binary search
    over one shift ``d`` for the whole batch finds the maximum in
    ``log2(E)`` passes, with no gather.  ``t_i + span`` saturates at the
    int32 maximum instead of wrapping.
    """
    z, e = t.shape
    big = jnp.iinfo(jnp.int32).max
    slot = jnp.arange(e, dtype=jnp.int32)
    suffix_min = jax.lax.cummin(jnp.where(valid, t, big), axis=1,
                                reverse=True)
    # slots up to the row's last valid one; a saturated bound must not
    # reach the trailing pad slots
    in_row = slot <= jnp.max(jnp.where(valid, slot, -1), axis=1,
                             keepdims=True)
    if span < big:
        bound = jnp.where(t > big - span, big, t + span)
    else:
        bound = jnp.full(t.shape, big, jnp.int32)
    suffix_min = jnp.concatenate([suffix_min, jnp.full_like(t, big)], axis=1)
    in_row = jnp.concatenate([in_row, jnp.zeros_like(in_row)], axis=1)

    def reaches(d):
        """Whether some valid lane's horizon reaches ``d`` slots on."""
        ahead = jax.lax.dynamic_slice(suffix_min, (0, d), (z, e))
        alive = jax.lax.dynamic_slice(in_row, (0, d), (z, e))
        return jnp.any(valid & alive & (ahead <= bound))

    def halve(_, lo_hi):
        lo, hi = lo_hi
        mid = (lo + hi + 1) // 2
        ok = reaches(mid)
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo, _ = jax.lax.fori_loop(0, max(e - 1, 1).bit_length(), halve,
                              (jnp.int32(0), jnp.int32(e - 1)))
    return jnp.where(valid.any(), lo + 1, 0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("delta", "l_max", "with_ts"))
def scan_zones(u, v, t, valid, *, delta: int, l_max: int,
               with_ts: bool = False) -> ZoneResult:
    """Run the expansion over a ``[Z, E]`` batch of padded zone streams.

    Loops over horizon offsets, not slots: trip 0 seeds every valid lane
    from its own edge, and trip ``d`` offers lane ``i`` edge ``i + d`` of
    its row, for every lane at once.  Each candidate sees the edges of its
    row after its seed in slot order, so every output equals a sweep of
    all ``E`` slots, but the loop stops after :func:`horizon_trips` trips,
    past which no edge can change an output.  Where a horizon spans its
    row the trip count is ``E``, a full sweep.

    Args:
      u, v, t: int32[Z, E] padded edge streams (time-ordered within a zone).
      valid:   bool[Z, E] real-edge mask.
      with_ts: also return per-step absorption timestamps (the co-mining
        path's input; the single-config path pays nothing for the flag).
    Returns:
      ZoneResult with per-seed final codes (padding slots have length 0)
      and the trip count the scan ran.
    """
    z, e = u.shape
    valid = valid.astype(bool)
    trips = horizon_trips(t, valid, span=delta * l_max)
    state = seed_state(u, v, t, valid, l_max=l_max, with_ts=with_ts)
    # lane i reads slot i + d: append E invalid slots so no read runs off
    # the row
    rows = tuple(jnp.concatenate([x, jnp.zeros_like(x)], axis=1)
                 for x in (u, v, t, valid))

    def body(d, state):
        edge = tuple(jax.lax.dynamic_slice(x, (0, d), (z, e)) for x in rows)
        return step(state, edge, delta=delta, l_max=l_max)

    state = jax.lax.fori_loop(1, trips, body, state)
    return ZoneResult(code=state.code, length=state.length, ts=state.ts,
                      trips=trips)


def scan_zone(u, v, t, valid, *, delta: int, l_max: int,
              with_ts: bool = False) -> ZoneResult:
    """:func:`scan_zones` over one zone's ``[E]`` padded edge stream."""
    res = scan_zones(*(jnp.asarray(x)[None] for x in (u, v, t, valid)),
                     delta=delta, l_max=l_max, with_ts=with_ts)
    return ZoneResult(code=res.code[0], length=res.length[0],
                      ts=None if res.ts is None else res.ts[0],
                      trips=res.trips)


def derive_lengths(length, ts, *, delta: int, l_max: int):
    """Prefix length of each dominating-sweep candidate under a smaller config.

    The config-lattice co-mining lemma: zone streams are time-sorted, so
    for ``delta <= delta_dom`` and ``l_max <= l_max_dom`` the process a
    smaller config would have mined for a candidate is exactly the longest
    prefix of the dominating config's absorbed edge sequence in which every
    consecutive absorption gap ``ts[k] - ts[k-1]`` is ``<= delta``, capped
    at ``l_max`` edges.  (While the two configs agree on a prefix they make
    identical extension decisions — extension needs a node overlap, a
    strictly increasing timestamp, and a gap ``<= delta``; the first
    dominating absorption whose gap exceeds the smaller ``delta`` also
    proves an intervening stream edge timed the smaller config out, because
    any in-between edge ``t'`` satisfies ``ts[k-1] <= t' <= ts[k]``.)

    Args:
      length: int32[...] dominating-sweep process lengths.
      ts:     int32[..., l_max_dom] absorption timestamps (``with_ts``).
    Returns:
      int32[...] prefix lengths under ``(delta, l_max)``; 0 stays 0.
    """
    l_dom = ts.shape[-1]
    if l_dom > 1:
        steps = jnp.arange(1, l_dom, dtype=jnp.int32)
        gaps = ts[..., 1:] - ts[..., :-1]
        ok = (steps < length[..., None]) & (gaps <= delta)
        run = jnp.cumprod(ok.astype(jnp.int32), axis=-1).sum(axis=-1)
    else:
        run = jnp.zeros_like(length)
    out = jnp.minimum(1 + run, l_max).astype(jnp.int32)
    return jnp.where(length > 0, out, 0)
