"""Pallas-fused congruence backend -- the third registered kernel backend.

The numpy and jax backends in ``repro.core.kernels_xp`` evaluate the
congruence pipeline as a chain of whole-array ops: every intermediate
(three raw roofline terms, three scaled terms, gamma, three idealized
alphas) is its own ``(A, V)`` array, materialized in host RAM or HBM
between steps.  At mega-sweep scale (V in the millions) that traffic, not
the arithmetic, is the cost.

This backend collapses the whole ``raw_times -> combine -> eq1 ->
congruence`` chain into ONE ``pl.pallas_call``: the grid tiles the variant
axis, each program pulls a ``(_M_ROWS, TILE_V)`` machine tile and the full
``(_P_ROWS, A)`` profile stack into VMEM, computes every intermediate
in-register/VMEM, and writes only the ``(_OUT_ROWS, A, TILE_V)`` result
tile back out -- no intermediate ever touches HBM.

Crucially the kernel BODY is not a new copy of the math: it calls the very
same ``congruence_kernel`` / ``step_time_kernel`` / ``default_beta_kernel``
functions from ``kernels_xp`` with ``xp = jax.numpy``, so the repo-wide
"one copy of the Eq. 1 math" invariant survives.  Pallas contributes the
fusion and tiling, not a re-derivation.

Precision: TPUs have no f64, so this backend computes in float32.  The
equivalence tests pin ``pallas == numpy`` to ~1e-3 (f32 epsilon amplified
by the Eq. 1 cancellation ``(alpha - beta) / (gamma - beta)``) instead of
the ~1e-12 the x64 jax backend achieves.

Interpret mode: where jax's default backend is the CPU (the tests run
with ``JAX_PLATFORMS=cpu``) the kernel runs under
``pallas_call(interpret=True)`` -- slower, but the same tiling and the same
f32 math.  On any other platform it is compiled; only an explicit
``PallasBackend(interpret=True)`` asks for the interpreter there.

Importing this module registers the backend; ``kernels_xp.get_backend``
also lazily imports it on first ``backend="pallas"`` request, so callers
never need to import it explicitly.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict

import numpy as np

from repro.core.kernels_xp import (
    Backend,
    CongruenceArrays,
    MachineArrays,
    ProfileArrays,
    congruence_kernel,
    default_beta_kernel,
    register_backend,
    step_time_kernel,
)
from repro.core.machine import IDEAL_EPS
from repro.core.spans import span

#: Largest variant-axis tile: one fused program scores (A, tile) cells
#: entirely in VMEM.  512 = 4 f32 sublane groups x 128 lanes.
TILE_V = 512

#: Bytes the double-buffered ``(_OUT_ROWS, A, tile)`` f32 output block may
#: take.  Half of v5e's 16 MiB scoped VMEM, leaving the rest to the input
#: blocks and the kernel's temporaries; ``_variant_tile`` shrinks the tile
#: as A grows so the block stays inside it (A <= 256 keeps 512).
_OUT_BLOCK_BYTES = 8 << 20

_P_ROWS = 7   # the 6 ProfileArrays fields + the (A,) beta target, stacked
_M_ROWS = 8   # the 8 MachineArrays fields, stacked
_OUT_ROWS = 8  # gamma, 3 alphas, LBCS/HRCS/ICS, aggregate

_LANES = 128  # f32 lane width; the variant axis is padded to a multiple


def _round_up(n: int, k: int) -> int:
    return ((n + k - 1) // k) * k


def _variant_tile(a: int, v: int, tile_max: int = TILE_V) -> int:
    """Variant tile for ``a`` apps and ``v`` variants: a lane multiple, at
    most ``tile_max``, no wider than the padded population, and small
    enough that the double-buffered output block fits ``_OUT_BLOCK_BYTES``.
    The floor is one lane group, which overflows the 16 MiB of scoped VMEM
    past about 2,000 apps: there the app axis itself needs tiling."""
    per_lane = 2 * _OUT_ROWS * _round_up(max(a, 1), 8) * 4
    fit = max(_LANES, (_OUT_BLOCK_BYTES // per_lane) // _LANES * _LANES)
    return min(tile_max, fit, _round_up(max(v, 1), _LANES))


def _profile_rows(p_ref) -> ProfileArrays:
    return ProfileArrays(*(p_ref[i] for i in range(6)))


def _machine_rows(m_ref) -> MachineArrays:
    return MachineArrays(*(m_ref[i] for i in range(_M_ROWS)))


# --------------------------------------------------------------------------- #
# Kernel bodies -- thin Ref plumbing around the shared kernels_xp math
# --------------------------------------------------------------------------- #


def _congruence_body(jnp, timing_model, eps, clamp, p_ref, m_ref, out_ref):
    """Fused pass over one (A, TILE_V) tile: every intermediate stays in VMEM."""
    out = congruence_kernel(jnp, _profile_rows(p_ref), _machine_rows(m_ref),
                            p_ref[6], timing_model, eps, clamp)
    out_ref[0] = out.gamma
    out_ref[1] = out.alpha_compute
    out_ref[2] = out.alpha_memory
    out_ref[3] = out.alpha_interconnect
    out_ref[4] = out.lbcs
    out_ref[5] = out.hrcs
    out_ref[6] = out.ics
    out_ref[7] = out.aggregate


def _step_time_body(jnp, timing_model, p_ref, m_ref, out_ref):
    out_ref[...] = step_time_kernel(
        jnp, _profile_rows(p_ref), _machine_rows(m_ref), timing_model)


def _default_beta_body(jnp, p_ref, m_ref, out_ref):
    out_ref[0] = default_beta_kernel(
        jnp, _profile_rows(p_ref), _machine_rows(m_ref))


# --------------------------------------------------------------------------- #
# The backend
# --------------------------------------------------------------------------- #


class PallasBackend(Backend):
    """Fused f32 Pallas evaluation, tiled over the variant axis.

    ``interpret=None`` (the default) selects interpreter mode only where
    jax's default backend is the CPU and compiles everywhere else.
    ``tile_v`` caps the variant tile per fused program (``_variant_tile``
    derives the actual tile from the app count and population; the
    variant axis is padded with benign 1.0 columns to a tile multiple and
    sliced on the way out).
    """

    name = "pallas"
    differentiable = False

    def __init__(self, interpret: bool = None, tile_v: int = TILE_V):
        import jax
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        self._jax, self._jnp, self._pl = jax, jnp, pl
        if interpret is None:
            interpret = jax.default_backend() == "cpu"
        self.interpret = bool(interpret)
        self.tile_v = int(tile_v)
        self._jit_cache: Dict[str, Callable] = {}

    # -- conversions ---------------------------------------------------- #

    def asarray(self, a):
        return self._jnp.asarray(a, dtype=self._jnp.float32)

    def to_numpy(self, a) -> np.ndarray:
        return np.asarray(a)

    # -- packing -------------------------------------------------------- #

    def _profile_stack(self, p: ProfileArrays, beta=None) -> np.ndarray:
        """Stack profile fields (and optionally beta) into one f32 matrix."""
        rows = list(p) + ([] if beta is None else [beta])
        return np.stack([np.asarray(r, dtype=np.float32) for r in rows])

    def _machine_stack(self, m: MachineArrays, a: int):
        """``(_M_ROWS, V_pad)`` f32 stack, padded to a tile multiple.

        Pad columns are all-1.0 machines: every rate and scale is positive,
        so the padded cells compute garbage-but-finite values that the
        output slice drops -- no NaN/inf ever enters the kernel.
        """
        stack = np.stack([np.asarray(f, dtype=np.float32) for f in m])
        v = stack.shape[1]
        tile = _variant_tile(a, v, self.tile_v)
        v_pad = _round_up(max(v, 1), tile)
        if v_pad != v:
            pad = np.ones((_M_ROWS, v_pad - v), dtype=np.float32)
            stack = np.concatenate([stack, pad], axis=1)
        return stack, tile, v

    # -- fused entry points --------------------------------------------- #

    def _jitted(self, key: str, fn: Callable, static) -> Callable:
        if key not in self._jit_cache:
            self._jit_cache[key] = self._jax.jit(fn, static_argnames=static)
        return self._jit_cache[key]

    def _tiled_call(self, body, p_stack, m_stack, tile: int, out_rows: int,
                    name: str):
        """One fused ``pallas_call`` named ``name`` over the variant grid.

        Shapes are static under jit, so the grid / specs are rebuilt only
        on retrace.  ``out_rows == 0`` means a 2-D ``(A, V)`` output (step
        time); otherwise the output is an ``(out_rows, A, V)`` stack.
        """
        pl = self._pl
        p_rows, a = p_stack.shape
        m_rows, v_pad = m_stack.shape
        grid = (v_pad // tile,)
        in_specs = [
            pl.BlockSpec((p_rows, a), lambda i: (0, 0)),
            pl.BlockSpec((m_rows, tile), lambda i: (0, i)),
        ]
        if out_rows:
            out_shape = self._jax.ShapeDtypeStruct(
                (out_rows, a, v_pad), self._jnp.float32)
            out_specs = pl.BlockSpec((out_rows, a, tile), lambda i: (0, 0, i))
        else:
            out_shape = self._jax.ShapeDtypeStruct(
                (a, v_pad), self._jnp.float32)
            out_specs = pl.BlockSpec((a, tile), lambda i: (0, i))
        return pl.pallas_call(
            body,
            out_shape=out_shape,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
            interpret=self.interpret,
            name=name,
        )(p_stack, m_stack)

    def _step_time_fn(self) -> Callable:
        return self._jitted(
            "step_time",
            lambda p_stack, m_stack, timing_model, tile: self._tiled_call(
                functools.partial(_step_time_body, self._jnp, timing_model),
                p_stack, m_stack, tile, 0, "step_time"),
            ("timing_model", "tile"))

    def _default_beta_fn(self) -> Callable:
        return self._jitted(
            "default_beta",
            lambda p_stack, m_stack: self._pl.pallas_call(
                functools.partial(_default_beta_body, self._jnp),
                out_shape=self._jax.ShapeDtypeStruct(
                    (1, p_stack.shape[1]), self._jnp.float32),
                interpret=self.interpret,
                name="default_beta",
            )(p_stack, m_stack),
            ())

    def _congruence_fn(self) -> Callable:
        return self._jitted(
            "congruence",
            lambda p_stack, m_stack, timing_model, eps, clamp, tile:
                self._tiled_call(
                    functools.partial(_congruence_body, self._jnp,
                                      timing_model, eps, clamp),
                    p_stack, m_stack, tile, _OUT_ROWS, "congruence"),
            ("timing_model", "eps", "clamp", "tile"))

    def step_time(self, p, m, timing_model="serial"):
        with span("stage"):
            p_stack = self._profile_stack(p)
            m_stack, tile, v = self._machine_stack(m, p_stack.shape[1])
            p_dev, m_dev = self.asarray(p_stack), self.asarray(m_stack)
        out = self._step_time_fn()(p_dev, m_dev, timing_model=timing_model,
                                   tile=tile)
        with span("fetch"):
            return self.to_numpy(out)[:, :v]

    def default_beta(self, p, m_ref):
        """Per-app beta via the same shared kernel, one ungridded call.

        The reference is a single variant, so there is nothing to tile --
        the whole (rows x 1) problem is one VMEM-resident program.
        """
        with span("stage"):
            p_stack = self.asarray(self._profile_stack(p))
            m_stack = self.asarray(
                np.stack([np.asarray(f, dtype=np.float32) for f in m_ref]))
        out = self._default_beta_fn()(p_stack, m_stack)
        with span("fetch"):
            return self.to_numpy(out)[0]

    def congruence(self, p, m, beta, timing_model="serial",
                   eps=IDEAL_EPS, clamp=False) -> CongruenceArrays:
        with span("stage"):
            p_stack = self._profile_stack(p, beta)
            m_stack, tile, v = self._machine_stack(m, p_stack.shape[1])
            p_dev, m_dev = self.asarray(p_stack), self.asarray(m_stack)
        out = self._congruence_fn()(
            p_dev, m_dev, timing_model=timing_model, eps=eps, clamp=clamp,
            tile=tile)
        with span("fetch"):
            out = self.to_numpy(out)[:, :, :v]
        return CongruenceArrays(
            gamma=out[0],
            beta=np.asarray(beta),
            alpha_compute=out[1],
            alpha_memory=out[2],
            alpha_interconnect=out[3],
            lbcs=out[4],
            hrcs=out[5],
            ics=out[6],
            aggregate=out[7],
        )

    # -- mesh-sharded statistics pass ----------------------------------- #

    def sharded_stats(self, p, m, beta, mesh, timing_model="serial",
                      clamp=False, pad_to=None):
        """ONE fused ``pallas_call`` with the variant axis split over ``mesh``.

        ``jax.shard_map`` hands each device its local slice of the machine
        stack (profiles replicated); the device runs the same gridded fused
        kernel as ``congruence`` over its slice, then reduces ON-DEVICE to
        the per-variant suite means and per-app min/argmin.  Global variant
        indices come from ``lax.axis_index`` -- pad and out-of-chunk
        columns are masked to ``+inf`` before the min, so the merge is
        exact.  Only the ``(V_local,)`` means and ``(A,)`` rows leave the
        device; the ``(A, V_local)`` score tile is never gathered.

        The host-side merge over the per-device ``(ndev, A)`` stacks picks
        the first row attaining the min, and each device's argmin is the
        first in its slice.  Rows come back in mesh-axis position, which is
        also slice order (``axis_index`` places each slice), whatever the
        device ids -- so the combined argmin is first-occurrence, matching
        the numpy reference.
        """
        ndev = int(mesh.size)
        v = int(np.asarray(m.peak_flops).shape[0])
        if v == 0:
            return None

        with span("stage"):
            # Per-device slice width: cover max(v, pad_to) variants,
            # rounded so every device holds the same tile-aligned slice.
            p_stack = self._profile_stack(p, beta)
            local = -(-max(v, int(pad_to or 0)) // ndev)
            tile = _variant_tile(p_stack.shape[1], local, self.tile_v)
            local_pad = _round_up(max(local, 1), tile)
            v_pad = local_pad * ndev

            m_stack = np.stack([np.asarray(f, dtype=np.float32) for f in m])
            if v_pad != v:
                pad = np.ones((_M_ROWS, v_pad - v), dtype=np.float32)
                m_stack = np.concatenate([m_stack, pad], axis=1)
            p_dev, m_dev = self.asarray(p_stack), self.asarray(m_stack)

        fn = self._sharded_stats_fn(mesh, v, local_pad, tile, timing_model,
                                    clamp)
        agg, mins, idxs = fn(p_dev, m_dev)
        with span("fetch"):
            agg = np.asarray(agg)[:v].astype(np.float64)
            mins = np.asarray(mins)      # (ndev, A)
            idxs = np.asarray(idxs)      # (ndev, A) global-within-chunk
        dev = np.argmin(mins, axis=0)    # first device attaining the min
        cols = np.arange(mins.shape[1])
        return (agg,
                mins[dev, cols].astype(np.float64),
                idxs[dev, cols].astype(np.int64))

    def _sharded_stats_fn(self, mesh, v: int, local_pad: int, tile: int,
                          timing_model: str, clamp: bool) -> Callable:
        """The jitted ``shard_map`` program behind ``sharded_stats``: maps
        ``(p_stack, m_stack)`` with ``m_stack`` of width ``local_pad`` per
        device to the per-device means, minima and argmins."""
        jax, jnp = self._jax, self._jnp
        from jax.sharding import PartitionSpec

        axis = mesh.axis_names[0]
        mesh_key = (axis, tuple(int(d.id) for d in mesh.devices.flat))
        key = f"sharded/{v}/{local_pad}/{tile}/{timing_model}/{clamp}/{mesh_key}"
        if key not in self._jit_cache:
            body = functools.partial(_congruence_body, jnp, timing_model,
                                     IDEAL_EPS, clamp)

            def local_stats(p_s, m_local):
                agg = self._tiled_call(body, p_s, m_local, tile,
                                       _OUT_ROWS, "congruence")[_OUT_ROWS - 1]
                lo = jax.lax.axis_index(axis) * local_pad
                valid = (lo + jnp.arange(local_pad)) < v
                masked = jnp.where(valid[None, :], agg, jnp.inf)
                return (agg.mean(axis=0),
                        masked.min(axis=1)[None, :],
                        (masked.argmin(axis=1) + lo)[None, :])

            self._jit_cache[key] = jax.jit(jax.shard_map(
                local_stats,
                mesh=mesh,
                in_specs=(PartitionSpec(), PartitionSpec(None, axis)),
                out_specs=(PartitionSpec(axis), PartitionSpec(axis),
                           PartitionSpec(axis)),
                # pallas_call's out_shape carries no varying-axes type
                check_vma=False,
            ))
        return self._jit_cache[key]


register_backend("pallas", PallasBackend)
