"""Pallas kernels for PTMT Phase-1 zone expansion.

Two kernels share one edge-update rule (:func:`_edge_update` — the single
copy of the paper's Definition 2-5 transition semantics in Pallas land):

**Dense per-zone kernel** (:func:`zone_scan_pallas`) — the seed layout.

  Layout (state in VMEM, lanes = candidates; inputs in SMEM):

    grid = (n_cand_blocks, n_edge_blocks)   # both sequential on TPU
    scratch: candidate SoA for ONE candidate block (VMEM) —
        length/last_t/done/n_nodes  int32[1, C_BLK]
        nodes                       int32[K, C_BLK]   K = l_max + 1
        code                        int32[L, C_BLK]   L = n_limbs(l_max)
    inputs per cell (SMEM, so the sweep reads each edge as scalars): one
        edge block (u, v, t, valid as int32[1, E_BLK]) plus the candidate
        block's seed times t_cand[1, C_BLK]
    outputs per candidate block: code int32[L, C_BLK], length int32[1, C_BLK]

  With the candidate axis OUTER, each candidate block streams the whole
  edge stream once and is flushed exactly once; scratch is a single block
  (~(K+L+4) * C_BLK * 4 bytes ≈ 50 KB at C_BLK=1024, l_max=6 — far under
  VMEM).  It is mined per zone (``vmap`` over a padded [Z, e_cap] batch),
  so a multi-bucket :class:`~repro.core.tzp.ZoneBatchLayout` costs one
  launch *per bucket*.

**Fused bucket-native kernel** (:func:`fused_zone_scan_flat`) — a single
launch whose 1-D grid spans *every* bucket of a layout at once.  The host
concatenates all buckets' padded zone rows into one flat slot stream
(``repro.core.tzp.concat_layout``); candidate blocks of ``blk`` lanes tile
the stream, and a per-block descriptor (``hi``) bounds each block's sweep
to the flat span of the zones its lanes belong to.  Blocks may straddle
zones and buckets: a per-slot ``zone_id`` gates every extension/seed/
time-out to same-zone edges, so inert padding rows and foreign zones are
masked rather than aligned away.  Candidate state lives in a pure
``fori_loop`` carry (no cross-grid-step scratch).  The per-block
descriptors arrive by scalar prefetch; the flat stream stays in HBM and
each live ``blk`` chunk is copied by DMA into SMEM, whose scalars the
sweep reads one edge at a time.

**Live-window block skipping** (beyond-paper, both kernels' key
optimization): a (candidate-block x edge-chunk) cell is skipped when

  * every edge index in the chunk precedes every candidate in the block
    (those candidates are not yet seeded: extensions need edge_idx > seed
    — the fused kernel gets this for free by starting each block's sweep
    at its own base), or
  * the chunk's earliest timestamp exceeds the block's last seed time by
    more than ``l_max * delta`` (every candidate's lifetime is over —
    Lemma 4.1's span bound).  The dense kernel reads the chunk's first
    timestamp (edges are time-sorted within a zone); the fused kernel
    compares a masked min over the chunk (computed per block before the
    launch), which stays conservative even where the concatenated stream
    is not globally time-sorted.

Edges are time-sorted within each zone, so a candidate is live for
~``1/omega`` of its zone and skipping turns the dense O(E^2) sweep into
O(E^2 / omega) — measured in EXPERIMENTS.md §Perf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import encoding
from repro.kernels.common import resolve_interpret

DIGITS_PER_LIMB = encoding.DIGITS_PER_LIMB

_I32_MIN = jnp.iinfo(jnp.int32).min
_I32_MAX = jnp.iinfo(jnp.int32).max


def _edge_update(state, *, u, v, t, seed, gate, delta, l_max, iota_k,
                 li_iota, iota_l=None):
    """Apply one edge to a candidate block's expansion state.

    The single copy of the Phase-1 transition rule shared by the dense and
    fused kernels.  ``state`` is ``(length, last_t, done, n_nodes, nodes,
    code)`` — int32 arrays of shape [1, C] (nodes [K, C], code [L, C]) —
    plus a trailing ``ts`` [l_max, C] absorption-timestamp block when
    ``iota_l`` (an int32[l_max, C] step iota) is given.

    Args:
      u, v, t: this edge's scalars (int32).
      seed: bool[1, C] — lanes seeded by this edge (its own slot; already
        gated on the edge being valid).
      gate: bool — per-lane eligibility of this edge for extension and
        time-out (edge validity, and for the fused kernel same-zone
        membership).  Scalar or [1, C]; broadcasting handles both.
      iota_l: step iota enabling per-step timestamp tracking (the config-
        lattice co-mining input); None keeps the 6-element state.
    """
    if iota_l is None:
        length, last_t, done, n_nodes, nodes, code = state
        ts = None
    else:
        length, last_t, done, n_nodes, nodes, code, ts = state
    k = iota_k.shape[0]

    active = (length > 0) & ~done
    gap_ok = (t > last_t) & (t - last_t <= delta)
    timed_out = active & (t - last_t > delta) & gate

    u_hit = nodes == u
    v_hit = nodes == v
    u_in = u_hit.any(axis=0, keepdims=True)
    v_in = v_hit.any(axis=0, keepdims=True)
    extend = (
        active & ~timed_out & gap_ok & (length < l_max)
        & (u_in | v_in) & gate
    )

    u_pos = jnp.min(jnp.where(u_hit, iota_k, k), axis=0, keepdims=True)
    v_pos = jnp.min(jnp.where(v_hit, iota_k, k), axis=0, keepdims=True)
    label_u = jnp.where(u_in, u_pos, n_nodes)
    nn1 = n_nodes + (~u_in).astype(jnp.int32)
    same_uv = u == v
    label_v = jnp.where(same_uv, label_u,
                        jnp.where(v_in, v_pos, nn1))
    nn2 = jnp.where(same_uv, nn1, nn1 + (~v_in).astype(jnp.int32))

    put_u = extend & ~u_in
    put_v = extend & ~v_in & ~same_uv
    nodes = jnp.where(put_u & (iota_k == n_nodes), u, nodes)
    nodes = jnp.where(put_v & (iota_k == nn1), v, nodes)

    # append the two digits (label+1) at positions 2*len, 2*len+1
    for which, label in ((0, label_u), (1, label_v)):
        pos = 2 * length + which
        limb_idx = pos // DIGITS_PER_LIMB
        shift = 4 * (DIGITS_PER_LIMB - 1 - pos % DIGITS_PER_LIMB)
        add = jnp.where(extend, jnp.left_shift(label + 1, shift), 0)
        code = code + jnp.where(li_iota == limb_idx, add, 0)

    new_length = length + extend.astype(jnp.int32)
    new_last_t = jnp.where(extend, t, last_t)
    new_nn = jnp.where(extend, nn2, n_nodes)

    # seed the candidate owned by this edge
    new_length = jnp.where(seed, 1, new_length)
    new_last_t = jnp.where(seed, t, new_last_t)
    new_nn = jnp.where(seed, jnp.where(same_uv, 1, 2), new_nn)
    nodes = jnp.where(seed & (iota_k == 0), u, nodes)
    nodes = jnp.where(seed & (iota_k == 1) & ~same_uv, v, nodes)
    seed_digit0 = 1 << (4 * (DIGITS_PER_LIMB - 1))
    seed_digit1 = jnp.where(same_uv, 1, 2) << (4 * (DIGITS_PER_LIMB - 2))
    seed_code = jnp.where(li_iota == 0, seed_digit0 + seed_digit1, 0)
    code = jnp.where(seed, seed_code, code)

    out = (new_length, new_last_t, done | timed_out, new_nn, nodes, code)
    if ts is None:
        return out
    # record this edge's timestamp at the step it was absorbed: row
    # `length` (pre-increment) for an extension, row 0 for a seed
    ts = jnp.where(extend & (iota_l == length), t, ts)
    ts = jnp.where(seed & (iota_l == 0), t, ts)
    return out + (ts,)


def _kernel(
    t_cand_ref, u_ref, v_ref, t_ref, valid_ref, *refs,
    delta: int, l_max: int, c_blk: int, e_blk: int, n_e_blocks: int,
    with_ts: bool,
):
    """One (candidate block, edge block) cell of the dense zone scan.

    Every input block is a ``[1, blk]`` row in SMEM: the candidate
    block's seed times ``t_cand`` and the edge block's ``u/v/t/valid``,
    so the per-edge sweep reads scalars (Mosaic cannot index a dynamic
    lane of a vector).
    """
    if with_ts:
        (code_out_ref, len_out_ref, ts_out_ref,
         length_ref, last_t_ref, done_ref, nn_ref, nodes_ref, code_ref,
         ts_ref) = refs
    else:
        (code_out_ref, len_out_ref,
         length_ref, last_t_ref, done_ref, nn_ref, nodes_ref,
         code_ref) = refs
        ts_out_ref = ts_ref = None
    ci = pl.program_id(0)
    ei = pl.program_id(1)
    k = l_max + 1
    limbs = code_ref.shape[0]

    @pl.when(ei == 0)
    def _init():
        length_ref[...] = jnp.zeros_like(length_ref)
        last_t_ref[...] = jnp.zeros_like(last_t_ref)
        done_ref[...] = jnp.zeros_like(done_ref)
        nn_ref[...] = jnp.zeros_like(nn_ref)
        nodes_ref[...] = jnp.full_like(nodes_ref, -1)
        code_ref[...] = jnp.zeros_like(code_ref)
        if ts_ref is not None:
            ts_ref[...] = jnp.zeros_like(ts_ref)

    c_base = ci * c_blk
    e_base = ei * e_blk
    # skip tests (see module docstring)
    index_live = e_base + e_blk - 1 >= c_base
    time_live = t_ref[0, 0] <= t_cand_ref[0, c_blk - 1] + l_max * delta

    @pl.when(index_live & time_live)
    def _sweep():
        iota_c = jax.lax.broadcasted_iota(jnp.int32, (1, c_blk), 1) + c_base
        iota_k = jax.lax.broadcasted_iota(jnp.int32, (k, c_blk), 0)
        li_iota = jax.lax.broadcasted_iota(jnp.int32, (limbs, c_blk), 0)
        iota_l = (jax.lax.broadcasted_iota(jnp.int32, (l_max, c_blk), 0)
                  if with_ts else None)

        def body(j, _):
            u = u_ref[0, j]
            v = v_ref[0, j]
            t = t_ref[0, j]
            valid = valid_ref[0, j] != 0

            state = (
                length_ref[...], last_t_ref[...], done_ref[...] != 0,
                nn_ref[...], nodes_ref[...], code_ref[...],
            )
            if with_ts:
                state = state + (ts_ref[...],)
            out = _edge_update(
                state, u=u, v=v, t=t,
                seed=(iota_c == e_base + j) & valid, gate=valid,
                delta=delta, l_max=l_max, iota_k=iota_k, li_iota=li_iota,
                iota_l=iota_l,
            )
            length, last_t, done, nn, nodes, code = out[:6]
            length_ref[...] = length
            last_t_ref[...] = last_t
            done_ref[...] = done.astype(jnp.int32)
            nn_ref[...] = nn
            nodes_ref[...] = nodes
            code_ref[...] = code
            if with_ts:
                ts_ref[...] = out[6]
            return 0

        jax.lax.fori_loop(0, e_blk, body, 0)

    @pl.when(ei == n_e_blocks - 1)
    def _flush():
        code_out_ref[...] = code_ref[...]
        len_out_ref[...] = length_ref[...]
        if ts_out_ref is not None:
            ts_out_ref[...] = ts_ref[...]


def zone_scan_pallas(
    u, v, t, valid, *, delta: int, l_max: int,
    c_blk: int = 512, e_blk: int = 256, interpret: bool | None = None,
    with_ts: bool = False,
):
    """Run the Pallas zone-scan over one padded zone.

    Args:
      u, v, t: int32[E]; valid: bool[E].  E is padded up to block multiples.
      with_ts: also return per-step absorption timestamps int32[E, l_max]
        (the config-lattice co-mining input).
    Returns:
      (code int32[E, L], length int32[E]) per seed candidate, plus
      ts int32[E, l_max] when ``with_ts``.
    """
    interpret = resolve_interpret(interpret)
    e = u.shape[0]
    limbs = encoding.n_limbs(l_max)
    k = l_max + 1

    blk = max(c_blk, e_blk)
    e_pad = -(-e // blk) * blk
    pad = e_pad - e
    valid_i = valid.astype(jnp.int32)
    if pad:
        u = jnp.pad(u, (0, pad))
        v = jnp.pad(v, (0, pad))
        t = jnp.pad(t, (0, pad))
        valid_i = jnp.pad(valid_i, (0, pad))
    # normalize padding timestamps (invalid slots) to the max valid time so
    # block skipping stays conservative; padded edges are semantically inert.
    t_fill = jnp.max(jnp.where(valid_i != 0, t, _I32_MIN))
    t = jnp.where(valid_i != 0, t, t_fill)

    n_c_blocks = e_pad // c_blk
    n_e_blocks = e_pad // e_blk
    # [n_blocks, 1, blk] rows: each [1, blk] SMEM block is one whole row,
    # so its last two dims equal the array's, as Mosaic's tiling rule asks
    rows = lambda x, b: x.reshape(e_pad // b, 1, b)
    smem = lambda b, index_map: pl.BlockSpec(
        (None, 1, b), index_map, memory_space=pltpu.SMEM)

    kernel = functools.partial(
        _kernel, delta=delta, l_max=l_max, c_blk=c_blk, e_blk=e_blk,
        n_e_blocks=n_e_blocks, with_ts=with_ts,
    )
    out_specs = [
        pl.BlockSpec((limbs, c_blk), lambda ci, ei: (0, ci)),
        pl.BlockSpec((1, c_blk), lambda ci, ei: (0, ci)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((limbs, e_pad), jnp.int32),
        jax.ShapeDtypeStruct((1, e_pad), jnp.int32),
    ]
    scratch_shapes = [
        pltpu.VMEM((1, c_blk), jnp.int32),      # length
        pltpu.VMEM((1, c_blk), jnp.int32),      # last_t
        pltpu.VMEM((1, c_blk), jnp.int32),      # done
        pltpu.VMEM((1, c_blk), jnp.int32),      # n_nodes
        pltpu.VMEM((k, c_blk), jnp.int32),      # nodes
        pltpu.VMEM((limbs, c_blk), jnp.int32),  # code
    ]
    if with_ts:
        out_specs.append(
            pl.BlockSpec((l_max, c_blk), lambda ci, ei: (0, ci)))
        out_shape.append(jax.ShapeDtypeStruct((l_max, e_pad), jnp.int32))
        scratch_shapes.append(pltpu.VMEM((l_max, c_blk), jnp.int32))  # ts
    outs = pl.pallas_call(
        kernel,
        grid=(n_c_blocks, n_e_blocks),
        in_specs=[
            smem(c_blk, lambda ci, ei: (ci, 0, 0)),   # t_cand
            smem(e_blk, lambda ci, ei: (ei, 0, 0)),   # u
            smem(e_blk, lambda ci, ei: (ei, 0, 0)),   # v
            smem(e_blk, lambda ci, ei: (ei, 0, 0)),   # t
            smem(e_blk, lambda ci, ei: (ei, 0, 0)),   # valid
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        name="zone_scan_dense",
    )(rows(t, c_blk), *(rows(x, e_blk) for x in (u, v, t, valid_i)))

    code, length = outs[0], outs[1]
    if with_ts:
        return code.T[:e], length[0, :e], outs[2].T[:e]
    return code.T[:e], length[0, :e]


# ---------------------------------------------------------------------------
# Fused bucket-native kernel: one launch over a concatenated ragged layout.
# ---------------------------------------------------------------------------


def _fused_kernel(
    lo_ref, hi_ref, bmin_ref, bmax_ref,
    u_hbm, v_hbm, t_hbm, valid_hbm, zid_hbm, lane_zid_ref,
    code_out_ref, len_out_ref, *refs,
    delta: int, l_max: int, blk: int, with_ts: bool,
):
    """One candidate block of the concatenated flat slot stream.

    Grid is 1-D over candidate blocks.  The per-block descriptors arrive
    by scalar prefetch (SMEM): the host-planned sweep span ``[lo, hi)``,
    each block's min valid edge time (``bmin``) and max valid seed time
    (``bmax``).  The flat edge stream stays in HBM; each live ``blk``
    chunk of the span is copied by DMA into SMEM, from which the sweep
    reads one edge's scalars per step.  Because the span can differ per
    block, the ragged layout is a *single* launch.  ``lo`` is the block's
    own base for live blocks (the sweep must pass over each lane's own
    slot to seed it) and equals ``hi`` for dead blocks (no valid lanes:
    zero chunks, outputs stay the zero init).  Candidate state is a pure
    ``fori_loop`` carry: no scratch persists across grid steps, so the
    kernel has no sequential-grid requirement.
    """
    if with_ts:
        ts_out_ref, *bufs = refs
    else:
        ts_out_ref, bufs = None, refs
    i = pl.program_id(0)
    base = i * blk
    limbs = code_out_ref.shape[0]
    k = l_max + 1

    lo = lo_ref[i]                          # blk-aligned sweep start
    hi = hi_ref[i]                          # blk-aligned sweep end
    lane_zid = lane_zid_ref[...]
    iota_lane = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1) + base
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (k, blk), 0)
    li_iota = jax.lax.broadcasted_iota(jnp.int32, (limbs, blk), 0)
    iota_l = (jax.lax.broadcasted_iota(jnp.int32, (l_max, blk), 0)
              if with_ts else None)

    # latest seed time among this block's real lanes: the Lemma-4.1 horizon
    horizon = bmax_ref[i] + l_max * delta

    state0 = (
        jnp.zeros((1, blk), jnp.int32),            # length
        jnp.zeros((1, blk), jnp.int32),            # last_t
        jnp.zeros((1, blk), jnp.int32),            # done (0/1)
        jnp.zeros((1, blk), jnp.int32),            # n_nodes
        jnp.full((k, blk), -1, jnp.int32),         # nodes
        jnp.zeros((limbs, blk), jnp.int32),        # code
    )
    if with_ts:
        state0 = state0 + (jnp.zeros((l_max, blk), jnp.int32),)  # ts

    def chunk_body(ci, state):
        off = lo + ci * blk

        # time skip: every valid edge in the chunk is beyond the horizon.
        # A masked min stays conservative on the (not globally time-sorted)
        # concatenated stream; the first chunk contains the lanes
        # themselves, so min <= horizon there and seeds are never lost.
        live = bmin_ref[off // blk] <= horizon

        def sweep(st):
            for src, buf in zip((u_hbm, v_hbm, t_hbm, valid_hbm, zid_hbm),
                                bufs):
                pltpu.sync_copy(src.at[off // blk], buf)
            cu, cv, ct, cvalid, czid = bufs

            def body(j, s):
                # the loop carries ``done`` as int32: Mosaic cannot carry
                # a bool vector through a loop
                evalid = cvalid[0, j] != 0
                out = _edge_update(
                    s[:2] + (s[2] != 0,) + s[3:], u=cu[0, j], v=cv[0, j],
                    t=ct[0, j], seed=(iota_lane == off + j) & evalid,
                    gate=evalid & (czid[0, j] == lane_zid),
                    delta=delta, l_max=l_max, iota_k=iota_k,
                    li_iota=li_iota, iota_l=iota_l,
                )
                return out[:2] + (out[2].astype(jnp.int32),) + out[3:]
            return jax.lax.fori_loop(0, blk, body, st)

        return jax.lax.cond(live, sweep, lambda s: s, state)

    # index skip is structural: the sweep starts at this block's own base
    # (edges before a candidate's seed slot can never extend it — within a
    # zone they are not strictly later in time), and ends at the host-
    # planned ``hi`` (zone end, or the Lemma-4.1 horizon cut when the
    # layout carries compacted bounds).  Dead blocks have lo == hi.
    n_chunks = (hi - lo) // blk
    state = jax.lax.fori_loop(0, n_chunks, chunk_body, state0)
    code_out_ref[...] = state[5]
    len_out_ref[...] = state[0]
    if with_ts:
        ts_out_ref[...] = state[6]


def fused_zone_scan_flat(
    u, v, t, valid, zone_id, lo, hi, *, delta: int, l_max: int,
    blk: int = 512, interpret: bool | None = None, with_ts: bool = False,
):
    """Single-launch ragged zone scan over a concatenated flat slot stream.

    Args:
      u, v, t: int32[S] flat edge slots — every bucket's padded [Z_b,
        e_cap_b] rows flattened and concatenated (see
        ``repro.core.tzp.concat_layout``).  S must be a multiple of
        ``blk``.
      valid: int32/bool[S] — real-edge mask (padding slots are 0).
      zone_id: int32[S] — owning zone row per slot (-1 for stream pad);
        gates extensions/seeds/time-outs to same-zone edges.
      lo, hi: int32[S // blk] — per candidate block, the blk-aligned
        host-planned sweep window ``[lo, hi)``: ``lo`` is the block's own
        base (``lo == hi`` for dead blocks), ``hi`` one past the last
        slot that can still affect any lane — the end of the last zone a
        lane belongs to, optionally tightened to the Lemma-4.1 time
        horizon (``bounds="live"`` in ``concat_layout``).

    Returns:
      (code int32[S, L], length int32[S]) per seed candidate slot, plus
      ts int32[S, l_max] absorption timestamps when ``with_ts``.
    """
    interpret = resolve_interpret(interpret)
    s_pad = u.shape[0]
    if s_pad % blk:
        raise ValueError(
            f"flat slot count {s_pad} is not a multiple of blk {blk}")
    n_blocks = s_pad // blk
    if lo.shape[0] != n_blocks or hi.shape[0] != n_blocks:
        raise ValueError(
            f"descriptors (lo: {lo.shape[0]}, hi: {hi.shape[0]}) do not "
            f"match {n_blocks} candidate blocks")
    limbs = encoding.n_limbs(l_max)

    stream = [jnp.asarray(x).astype(jnp.int32)
              for x in (u, v, t, valid, zone_id)]
    t_i, valid_i, zid_i = stream[2], stream[3], stream[4]
    # per-block time bounds: min valid edge time (the chunk skip test) and
    # max valid seed time (the block's horizon), one scalar each in SMEM
    valid_t = lambda fill: jnp.where(valid_i != 0, t_i, fill).reshape(
        n_blocks, blk)
    bmin = jnp.min(valid_t(_I32_MAX), axis=1)
    bmax = jnp.max(valid_t(_I32_MIN), axis=1)

    per_block = lambda rows: pl.BlockSpec((rows, blk), lambda i, *_: (0, i))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)

    kernel = functools.partial(
        _fused_kernel, delta=delta, l_max=l_max, blk=blk, with_ts=with_ts,
    )
    out_specs = [per_block(limbs), per_block(1)]
    out_shape = [
        jax.ShapeDtypeStruct((limbs, s_pad), jnp.int32),
        jax.ShapeDtypeStruct((1, s_pad), jnp.int32),
    ]
    if with_ts:
        out_specs.append(per_block(l_max))
        out_shape.append(jax.ShapeDtypeStruct((l_max, s_pad), jnp.int32))
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,               # lo, hi, bmin, bmax
            grid=(n_blocks,),
            in_specs=[hbm] * 5 + [per_block(1)],  # stream; lane zone ids
            out_specs=out_specs,
            scratch_shapes=[pltpu.SMEM((1, blk), jnp.int32)] * 5,
        ),
        out_shape=out_shape,
        interpret=interpret,
        name="zone_scan_fused",
    )(jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32), bmin, bmax,
      *(x.reshape(n_blocks, 1, blk) for x in stream),
      zid_i.reshape(1, s_pad))

    code, length = outs[0], outs[1]
    if with_ts:
        return code.T, length[0], outs[2].T
    return code.T, length[0]
