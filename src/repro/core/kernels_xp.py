"""Backend-agnostic congruence kernels -- ONE copy of the timing/Eq. 1 math.

Before this module the repo carried two implementations of the paper's
analytic core: the scalar reference in ``timing.py``/``congruence.py`` and
the struct-of-arrays NumPy kernels in ``sweep.py``, kept bit-equal only by
tests.  Here the roofline terms, Eq. 1, the default-beta rule and the L2
aggregate are written once against an array-namespace handle ``xp`` and
evaluated through a registered ``Backend``:

  * ``numpy`` -- eager float64 NumPy; the default, byte-for-byte the old
    behavior.  Scalar callers (``timing.subsystem_times``,
    ``congruence.profile_congruence``) run the same kernels at batch size 1.
  * ``jax``   -- ``jit``-compiled, device-placed ``jax.numpy`` under x64 so
    results match NumPy to ~1e-12.  Because the whole pipeline is traced,
    it is also differentiable end-to-end (``repro.core.codesign`` takes
    ``jax.grad`` through it).

Backend selection: explicit ``backend=`` argument > ``REPRO_SWEEP_BACKEND``
environment variable > ``numpy``.

Data layout: kernels consume ``ProfileArrays`` (shape ``(A,)`` per field)
and ``MachineArrays`` (shape ``(V,)`` per field) namedtuples -- both are
JAX pytrees, so the jitted entry points retrace only on shape changes.
All (A,)x(V,) kernels broadcast to ``(A, V)``.
"""

from __future__ import annotations

import os
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import numpy as np

from repro.core.machine import IDEAL_EPS

DEFAULT_BACKEND_ENV = "REPRO_SWEEP_BACKEND"


class ProfileArrays(NamedTuple):
    """``A`` workload profiles, one array per field the timing model reads.

    ``mem_bytes`` carries the scalar path's fallback (``hbm_bytes`` when
    positive, else raw ``bytes_accessed``) applied at pack time.
    """

    flops: object
    mem_bytes: object
    collective_bytes: object
    pod_collective_bytes: object
    model_flops: object
    num_devices: object


class MachineArrays(NamedTuple):
    """``V`` machine variants, one array per model constant."""

    peak_flops: object
    hbm_bw: object
    ici_bw: object
    ici_links: object
    inter_pod_bw: object
    scale_compute: object
    scale_memory: object
    scale_interconnect: object

    @property
    def ici_bw_total(self):
        return self.ici_bw * self.ici_links


class CongruenceArrays(NamedTuple):
    """One full congruence pass: everything ``SweepResult`` stores, as
    ``(A, V)`` arrays (``beta`` is the ``(A,)`` per-app target)."""

    gamma: object
    beta: object
    alpha_compute: object
    alpha_memory: object
    alpha_interconnect: object
    lbcs: object
    hrcs: object
    ics: object
    aggregate: object


# --------------------------------------------------------------------------- #
# The kernels (single source of truth for the paper's math)
# --------------------------------------------------------------------------- #


def raw_times(xp, p: ProfileArrays, m: MachineArrays) -> Tuple[object, object, object]:
    """Unscaled per-subsystem roofline terms, each shaped ``(A, V)``.

    compute      = per-device HLO FLOPs / peak FLOP/s
    memory       = per-device HLO bytes / HBM BW
    interconnect = per-device collective bytes / ICI BW, with traffic that
                   crosses the pod axis charged at the slower inter-pod rate.

    The per-subsystem delay scales are factored out so idealization
    (replacing one scale with ``eps``) is a multiply, not a re-evaluation.
    """
    raw_c = p.flops[:, None] / m.peak_flops[None, :]
    raw_m = p.mem_bytes[:, None] / m.hbm_bw[None, :]
    ici_bytes = p.collective_bytes - p.pod_collective_bytes
    t_ici = ici_bytes[:, None] / m.ici_bw_total[None, :]
    pod = p.pod_collective_bytes[:, None]
    t_pod = xp.where(pod != 0.0, pod / m.inter_pod_bw[None, :], 0.0)
    raw_i = t_ici + t_pod
    return raw_c, raw_m, raw_i


def scaled_times(xp, p: ProfileArrays, m: MachineArrays) -> Tuple[object, object, object]:
    """Per-subsystem times under the machine's (possibly idealized) scales."""
    raw_c, raw_m, raw_i = raw_times(xp, p, m)
    return (m.scale_compute[None, :] * raw_c,
            m.scale_memory[None, :] * raw_m,
            m.scale_interconnect[None, :] * raw_i)


def combine(xp, tc, tm, ti, timing_model: str):
    """Fold the three terms into a step time (DESIGN.md §2).

    ``serial``  -- t = tc + tm + ti (paper critical-path semantics).
    ``overlap`` -- t = max(terms), the Roofline ideal.
    """
    if timing_model == "serial":
        return tc + tm + ti
    if timing_model == "overlap":
        return xp.maximum(xp.maximum(tc, tm), ti)
    raise ValueError(f"unknown timing model {timing_model!r}")


def step_time_kernel(xp, p: ProfileArrays, m: MachineArrays,
                     timing_model: str = "serial"):
    """``(A, V)`` step-time matrix."""
    return combine(xp, *scaled_times(xp, p, m), timing_model)


def eq1(xp, alpha, gamma, beta):
    """Paper Eq. 1 over arrays, with the gamma == beta degeneracy -> 0.

        Score_i = 1 - (alpha_i - beta_i) / (gamma_i - beta_i)
    """
    denom = gamma - beta
    safe = xp.where(denom == 0.0, 1.0, denom)
    return xp.where(denom == 0.0, 0.0, 1.0 - (alpha - beta) / safe)


def default_beta_kernel(xp, p: ProfileArrays, m_ref: MachineArrays):
    """Per-app default target beta against reference variant column 0.

    The paper's beta is a user-defined target delay held constant across
    variants; our default is the ideal-compute time (useful model FLOPs at
    full MXU peak), floored at half the reference gamma so Eq. 1 stays
    meaningful, with a 5%-of-gamma fallback when model FLOPs are unknown.
    Always evaluated against the *serial* baseline, matching the scalar
    ``congruence.default_beta``.
    """
    tc, tm, ti = scaled_times(xp, p, m_ref)
    gamma_ref = (tc + tm + ti)[:, 0]
    valid = (p.model_flops > 0) & (p.num_devices > 0)
    denom = xp.where(valid, p.num_devices * m_ref.peak_flops[0], 1.0)
    t_ideal = xp.where(valid, p.model_flops / denom, xp.inf)
    return xp.where(valid, xp.minimum(t_ideal, 0.5 * gamma_ref),
                    0.05 * gamma_ref)


def congruence_kernel(
    xp,
    p: ProfileArrays,
    m: MachineArrays,
    beta,
    timing_model: str = "serial",
    eps: float = IDEAL_EPS,
    clamp: bool = False,
) -> CongruenceArrays:
    """One full congruence pass over the ``(A, V)`` cross-product.

    gamma, the three idealized alphas (each a scale substitution on the
    precomputed raw terms), the Eq. 1 scores and the L2 aggregate (paper
    §III-C: lower = smaller radar area = better fit), in one traceable
    expression.  ``beta`` is the ``(A,)`` per-app target.
    """
    raw = raw_times(xp, p, m)
    scales = (m.scale_compute, m.scale_memory, m.scale_interconnect)
    scaled = tuple(s[None, :] * r for s, r in zip(scales, raw))
    gamma = combine(xp, *scaled, timing_model)
    beta_col = beta[:, None]

    alphas = []
    scores = []
    for k in range(3):
        terms = list(scaled)
        terms[k] = eps * raw[k]
        alpha = combine(xp, *terms, timing_model)
        score = eq1(xp, alpha, gamma, beta_col)
        if clamp:
            score = xp.clip(score, 0.0, 1.0)
        alphas.append(alpha)
        scores.append(score)

    aggregate = xp.sqrt(scores[0] ** 2 + scores[1] ** 2 + scores[2] ** 2)
    return CongruenceArrays(
        gamma=gamma,
        beta=beta,
        alpha_compute=alphas[0],
        alpha_memory=alphas[1],
        alpha_interconnect=alphas[2],
        lbcs=scores[0],
        hrcs=scores[1],
        ics=scores[2],
        aggregate=aggregate,
    )


# --------------------------------------------------------------------------- #
# Backend registry
# --------------------------------------------------------------------------- #


class Backend:
    """One array-namespace evaluation strategy for the kernels above.

    Subclasses provide ``asarray``/``to_numpy`` conversion and may wrap the
    kernel entry points (jit, device placement, error-state management).
    """

    name: str = "abstract"
    #: True when the backend supports ``jax.grad`` through the kernels.
    differentiable: bool = False

    # -- conversions ---------------------------------------------------- #

    def asarray(self, a):
        raise NotImplementedError

    def to_numpy(self, a) -> np.ndarray:
        raise NotImplementedError

    def profile_arrays(self, p: ProfileArrays) -> ProfileArrays:
        return ProfileArrays(*(self.asarray(f) for f in p))

    def machine_arrays(self, m: MachineArrays) -> MachineArrays:
        return MachineArrays(*(self.asarray(f) for f in m))

    # -- kernel entry points -------------------------------------------- #

    def step_time(self, p: ProfileArrays, m: MachineArrays,
                  timing_model: str = "serial") -> np.ndarray:
        raise NotImplementedError

    def default_beta(self, p: ProfileArrays, m_ref: MachineArrays) -> np.ndarray:
        raise NotImplementedError

    def congruence(self, p: ProfileArrays, m: MachineArrays, beta,
                   timing_model: str = "serial", eps: float = IDEAL_EPS,
                   clamp: bool = False) -> CongruenceArrays:
        """Run the full pass and return *NumPy* ``CongruenceArrays``."""
        raise NotImplementedError

    def sharded_stats(self, p: ProfileArrays, m: MachineArrays, beta, mesh,
                      timing_model: str = "serial", clamp: bool = False,
                      pad_to: Optional[int] = None):
        """Mesh-sharded, gather-free statistics pass over one variant chunk.

        The mega-sweep reduction: score the ``(A, V_chunk)`` cross-product
        with the variant axis split over ``mesh`` and reduce ON-DEVICE to
        the three statistics ``shard_sweep`` merges -- per-variant
        suite-mean aggregates ``(V_chunk,)``, per-app minima ``(A,)`` and
        per-app argmin indices ``(A,)`` (0-based within the chunk).  Only
        those O(V) + O(A) rows ever cross devices; the score tensor stays
        sharded and is discarded.

        ``pad_to`` is a chunk-width hint: implementations pad the variant
        axis up to at least ``pad_to`` (with benign machines, masked out of
        the reductions) so equal-width chunks of a sharded loop share ONE
        compiled artifact instead of retracing per remainder chunk.

        Backends without a distribution strategy return ``None`` --
        ``shard_sweep`` then falls back to the host-chunked loop.  The
        ``jax`` backend shards via ``NamedSharding`` placement; the
        ``pallas`` backend runs its fused kernel under ``jax.shard_map``
        (see ``repro.core.kernels_pallas``).
        """
        return None


class NumpyBackend(Backend):
    """Eager float64 NumPy -- the default and the numerical reference."""

    name = "numpy"

    def asarray(self, a):
        return np.asarray(a, dtype=np.float64)

    def to_numpy(self, a) -> np.ndarray:
        return np.asarray(a)

    def step_time(self, p, m, timing_model="serial"):
        with np.errstate(divide="ignore", invalid="ignore"):
            return step_time_kernel(np, p, m, timing_model)

    def default_beta(self, p, m_ref):
        with np.errstate(divide="ignore", invalid="ignore"):
            return default_beta_kernel(np, p, m_ref)

    def congruence(self, p, m, beta, timing_model="serial",
                   eps=IDEAL_EPS, clamp=False):
        with np.errstate(divide="ignore", invalid="ignore"):
            return congruence_kernel(np, p, m, self.asarray(beta),
                                     timing_model, eps, clamp)


class JaxBackend(Backend):
    """``jax.numpy`` under x64 with jitted entry points.

    Each entry point is compiled once per (shape, static-config) and placed
    on the default device; x64 keeps results within ~1e-12 of the NumPy
    reference (tests pin 1e-6, comfortably met).  The same traced kernels
    power the gradient co-design mode in ``repro.core.codesign``.
    """

    name = "jax"
    differentiable = True

    def __init__(self):
        import jax
        import jax.numpy as jnp

        self._jax = jax
        self._jnp = jnp
        self._jit_cache: Dict[str, Callable] = {}

    def _x64(self):
        """Context manager every x64 call site (codesign, frontier, ...)
        enters before tracing or placing arrays."""
        return self._jax.enable_x64(True)

    def asarray(self, a):
        with self._x64():
            return self._jnp.asarray(a, dtype=self._jnp.float64)

    def to_numpy(self, a) -> np.ndarray:
        return np.asarray(a)

    def _jitted(self, key: str, fn: Callable, static: Tuple[str, ...]) -> Callable:
        if key not in self._jit_cache:
            self._jit_cache[key] = self._jax.jit(fn, static_argnames=static)
        return self._jit_cache[key]

    def step_time(self, p, m, timing_model="serial"):
        with self._x64():
            fn = self._jitted(
                "step_time",
                lambda p, m, timing_model: step_time_kernel(
                    self._jnp, p, m, timing_model),
                ("timing_model",))
            out = fn(self.profile_arrays(p), self.machine_arrays(m),
                     timing_model=timing_model)
            return self.to_numpy(out)

    def default_beta(self, p, m_ref):
        with self._x64():
            fn = self._jitted(
                "default_beta",
                lambda p, m: default_beta_kernel(self._jnp, p, m), ())
            return self.to_numpy(
                fn(self.profile_arrays(p), self.machine_arrays(m_ref)))

    def _congruence_fn(self) -> Callable:
        return self._jitted(
            "congruence",
            lambda p, m, beta, timing_model, eps, clamp: congruence_kernel(
                self._jnp, p, m, beta, timing_model, eps, clamp),
            ("timing_model", "eps", "clamp"))

    def congruence(self, p, m, beta, timing_model="serial",
                   eps=IDEAL_EPS, clamp=False):
        with self._x64():
            out = self._congruence_fn()(
                self.profile_arrays(p), self.machine_arrays(m),
                self.asarray(beta), timing_model=timing_model,
                eps=eps, clamp=clamp)
            return CongruenceArrays(*(self.to_numpy(f) for f in out))

    def sharded_stats(self, p, m, beta, mesh, timing_model="serial",
                      clamp=False, pad_to=None):
        """Shard the variant axis over ``mesh`` via ``NamedSharding``.

        Machine columns are placed split along the mesh axis, profiles and
        beta replicated; the jitted reduction then runs SPMD and only the
        ``(V_chunk,)`` means plus ``(A,)`` min/argmin rows come back to the
        host.  The chunk is padded (all-1.0 machines, masked to ``+inf``
        before the min/argmin) to a multiple of the device count and at
        least ``pad_to`` so every equal-width chunk reuses one executable.
        """
        jax, jnp = self._jax, self._jnp
        from jax.sharding import NamedSharding, PartitionSpec

        axis = mesh.axis_names[0]
        ndev = int(mesh.size)
        v = int(np.asarray(m.peak_flops).shape[0])
        if v == 0:
            return None
        v_pad = max(v, int(pad_to or 0))
        v_pad = -(-v_pad // ndev) * ndev

        with self._x64():
            split = NamedSharding(mesh, PartitionSpec(axis))
            rep = NamedSharding(mesh, PartitionSpec())

            def _col(f):
                arr = np.asarray(f, dtype=np.float64)
                if v_pad != v:
                    arr = np.concatenate([arr, np.ones(v_pad - v)])
                return jax.device_put(jnp.asarray(arr), split)

            m_dev = MachineArrays(*(_col(f) for f in m))
            p_dev = ProfileArrays(
                *(jax.device_put(self.asarray(f), rep) for f in p))
            beta_dev = jax.device_put(self.asarray(beta), rep)

            agg, app_min, app_idx = self._sharded_stats_fn(v, v_pad)(
                p_dev, m_dev, beta_dev, timing_model=timing_model,
                clamp=clamp)
            return (np.asarray(agg)[:v],
                    np.asarray(app_min),
                    np.asarray(app_idx).astype(np.int64))

    def _sharded_stats_fn(self, v: int, v_pad: int) -> Callable:
        """The jitted reduction behind ``sharded_stats`` for a chunk of
        ``v`` variants padded to ``v_pad``; the placement of its arguments
        decides how it is partitioned."""
        jnp = self._jnp

        def stats(p, m, beta, timing_model, clamp):
            out = congruence_kernel(jnp, p, m, beta, timing_model,
                                    clamp=clamp)
            masked = jnp.where(jnp.arange(v_pad)[None, :] < v,
                               out.aggregate, jnp.inf)
            return (out.aggregate.mean(axis=0),
                    masked.min(axis=1),
                    masked.argmin(axis=1))

        return self._jitted(f"sharded_stats/{v}/{v_pad}", stats,
                            ("timing_model", "clamp"))


_BACKEND_FACTORIES: Dict[str, Callable[[], Backend]] = {
    "numpy": NumpyBackend,
    "jax": JaxBackend,
}
_BACKEND_CACHE: Dict[str, Backend] = {}

#: Backends registered by a module that is only imported on first use, so
#: ``import repro.core`` stays light.  The module's import must call
#: ``register_backend`` under the same name.
_LAZY_BACKENDS: Dict[str, str] = {
    "pallas": "repro.core.kernels_pallas",
}


def register_backend(name: str, factory: Callable[[], Backend]) -> None:
    """Register a new backend factory (see the Pallas-fused path in
    ``repro.core.kernels_pallas`` for the worked example, and
    ``docs/backends.md`` for the contract)."""
    _BACKEND_FACTORIES[name] = factory
    _BACKEND_CACHE.pop(name, None)


def available_backends() -> Tuple[str, ...]:
    """Every selectable backend name, lazily-registered ones included."""
    return tuple(sorted(set(_BACKEND_FACTORIES) | set(_LAZY_BACKENDS)))


def validate_backend_name(name: Optional[str]) -> None:
    """Reject an unknown backend name with a ``ValueError``.

    The registry is open (``register_backend``), so callers can't bake a
    static choices list; every entry point -- CLIs via
    ``validate_backend_arg``, ``CodesignSpec.validate()``, the serving
    front door -- funnels through this one check so a bogus name fails
    with the registry's current contents instead of deep inside
    ``get_backend`` after expensive work.  ``None`` and constructed
    ``Backend`` instances pass (both are valid ``backend=`` values).
    """
    if isinstance(name, Backend) or name is None:
        return
    if name.lower() not in available_backends():
        raise ValueError(f"unknown backend {name!r}; available: "
                         f"{', '.join(available_backends())}")


def validate_backend_arg(parser, name: Optional[str]) -> None:
    """argparse wrapper over ``validate_backend_name``: reject an unknown
    ``--backend`` at parse time with the CLI's usage message."""
    try:
        validate_backend_name(name)
    except ValueError as e:
        parser.error(str(e))


def get_backend(name: Optional[str] = None) -> Backend:
    """Resolve a backend: explicit name > $REPRO_SWEEP_BACKEND > numpy.

    Passing an already-constructed ``Backend`` returns it unchanged, so
    every ``backend=`` parameter accepts either form.
    """
    if isinstance(name, Backend):
        return name
    if name is None:
        name = os.environ.get(DEFAULT_BACKEND_ENV, "") or "numpy"
    name = name.lower()
    if name not in _BACKEND_FACTORIES and name in _LAZY_BACKENDS:
        import importlib

        importlib.import_module(_LAZY_BACKENDS[name])
    if name not in _BACKEND_FACTORIES:
        raise ValueError(
            f"unknown backend {name!r}; have {available_backends()}")
    if name not in _BACKEND_CACHE:
        _BACKEND_CACHE[name] = _BACKEND_FACTORIES[name]()
    return _BACKEND_CACHE[name]
