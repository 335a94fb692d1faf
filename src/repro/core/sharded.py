"""Distributed coreset construction and evaluation on a JAX mesh.

The construction is embarrassingly parallel over row bands (coresets of
disjoint sub-signals compose exactly — see streaming.py).  On a real
cluster each host builds the coreset of the row band whose data it owns
(data never leaves the host: only the tiny coresets are gathered), which is
how the paper's challenge (iv) (parallel training of a single tree) is met.
In one process the per-band builds run on a thread pool (NumPy releases
the GIL in the hot loops), and ``fitting_loss_batched`` evaluates MANY
candidate trees at once (the hyperparameter-tuning inner loop) with blocks
sharded over a device mesh and one psum at the end.

JAX is imported only inside the mesh path: the serving front end and the
SDK import this module, and a process that only drives servers must not
load JAX.
"""
from __future__ import annotations

import dataclasses
# named here: ``concurrent.futures`` imports its thread module on first
# use, ~3 ms that fell inside the first build's trace between its phases
from concurrent.futures import Executor, ThreadPoolExecutor

import numpy as np

from repro import obs

from .bicriteria import BicriteriaResult
from .coreset import (SignalCoreset, band_bound, greedy_loss, practical_sigma,
                      signal_coreset)
from .stats import PrefixStats
from .streaming import compose, recompress

__all__ = ["sharded_coreset", "SharedSigma", "shared_sigma",
           "shared_tolerance", "certify_shared", "band_bounds",
           "fitting_loss_batched"]


@dataclasses.dataclass(frozen=True)
class SharedSigma:
    """The sigma a band-parallel build shares: :func:`practical_sigma` of
    ``bound``, the sum of the bands' O(N) row/column bounds
    (``band_bics``), and ``greedy``, the loss of a greedy k-tree over the
    whole signal.  The restriction of any k-segmentation to a row band is
    a <= k-segmentation of that band, so ``opt_k(D) >= sum_b opt_k(band_b)
    >= bound``: the sum is a certified lower bound on opt_k of the whole
    signal, as a single build's bicriteria bound is on its own."""

    greedy: float
    band_bics: tuple[BicriteriaResult, ...]

    @property
    def bound(self) -> float:
        # summed in band order, as certify_shared sums the bands' sigma
        return float(sum(b.sigma for b in self.band_bics))

    @property
    def sigma(self) -> float:
        return practical_sigma(self.bound, self.greedy)[0]

    @property
    def certified(self) -> bool:
        return practical_sigma(self.bound, self.greedy)[1]


def shared_sigma(values: np.ndarray, k: int, bands: list[tuple[int, int]],
                 *, pool: Executor | None = None,
                 _stats=None) -> SharedSigma:
    """The global sigma of a band-parallel build (:class:`SharedSigma`).
    The band bounds run on ``pool`` (a private one if None) while this
    thread runs the greedy pass.  Every band-parallel caller, the
    thread-pool path below and the cluster coordinator's scatter/gather,
    computes it here, so both derive the *identical* tolerance float,
    which is what keeps their composed coresets bitwise fingerprint-equal.
    """
    if pool is None:
        with ThreadPoolExecutor(max_workers=len(bands)) as own:
            return shared_sigma(values, k, bands, pool=own, _stats=_stats)
    y = np.asarray(values, np.float64)
    with obs.cpu_span("core.shared_tolerance") as sp:
        parent = obs.current_span()

        def bound(i: int, r0: int, r1: int) -> BicriteriaResult:
            with obs.attach(parent):
                return band_bound(y[r0:r1], k, band=i)

        futs = [pool.submit(bound, i, r0, r1)
                for i, (r0, r1) in enumerate(bands)]
        ps = _stats if _stats is not None else PrefixStats.build(y)
        sh = SharedSigma(greedy_loss(ps, k), tuple(f.result() for f in futs))
        sp.set_attr("sigma_greedy", sh.greedy / 6.0)
        sp.set_attr("sigma_bound", sh.bound)
        sp.set_attr("certified", sh.certified)
    return sh


def shared_tolerance(sigma: float, k: int, eps: float) -> float:
    """The global per-block opt1 cap (``tolerance_override``) from the
    shared sigma: the Lemma-14 budget ``eps^2 * sigma / k``, split over
    intersected blocks globally."""
    return eps * eps * sigma / max(k, 1)


def certify_shared(parts: list[SignalCoreset], row_offsets: list[int],
                   n_total: int, sigma_shared: float) -> SignalCoreset:
    """Compose band coresets built under the shared tolerance and decide
    the guarantee once: ``certified`` iff ``sigma_shared <= sum of the
    bands' sigma``.  Each band's sigma is a certified lower bound on its
    own opt_k, and the restriction of any k-segmentation of the signal to
    a row band is a <= k-segmentation of that band, so
    ``opt_k(D) >= sum_b opt_k(band_b) >= sum_b sigma_b``.  A sigma from
    :func:`shared_sigma` is that sum unless its heuristic floor engaged."""
    with obs.cpu_span("core.compose") as sp:
        cs = compose(parts, row_offsets, n_total=n_total)
        bound = float(sum(p.sigma for p in parts))
        sp.set_attr("sigma_shared", float(sigma_shared))
        sp.set_attr("sigma_bound", bound)
        sp.set_attr("blocks", cs.num_blocks)
    return dataclasses.replace(cs, certified=bool(sigma_shared <= bound))


def band_bounds(n: int, num_bands: int) -> list[tuple[int, int]]:
    """The canonical row-band split: linspace bounds, empty bands dropped.
    Shared by the thread-pool composer and the cluster's band-ownership map
    (worker i owns band i) so both partitions are always identical."""
    bounds = np.linspace(0, n, num_bands + 1).astype(int)
    return [(int(bounds[i]), int(bounds[i + 1])) for i in range(num_bands)
            if bounds[i + 1] > bounds[i]]


def sharded_coreset(values: np.ndarray, k: int, eps: float, num_bands: int,
                    *, recompress_result: bool = False, max_workers: int | None = None,
                    share_tolerance: bool = True, _stats=None, **kw) -> SignalCoreset:
    """Build per-row-band coresets in parallel and compose them.

    ``share_tolerance``: derive the per-block opt1 cap from a *global* sigma
    (:func:`shared_sigma` — on a real cluster, a tree-reduction over band
    statistics) and share it across bands.  The Lemma-14 error budget sums
    over intersected blocks globally, so a global cap keeps |C| at the
    single-build size; per-band caps (share_tolerance=False, the pure
    merge-reduce setting) are also valid but ~bands-times larger.  Each
    band takes its bound from the shared sigma's pass (``bound``) instead
    of computing it again, but in the ``paper`` fidelity, whose
    ``max_slices`` reads each band's full bicriteria.

    ``_stats`` (internal): prebuilt full-signal integral images for the
    sigma estimate — the serving engine maintains them incrementally via
    ``delta_sat``, sparing every rebuild of a mutating signal the O(N)
    from-scratch re-SAT here.
    """
    y = np.asarray(values, np.float64)
    bands = band_bounds(y.shape[0], num_bands)
    shared = share_tolerance and "tolerance_override" not in kw
    band_kw = [kw] * len(bands)
    with ThreadPoolExecutor(max_workers=max_workers or len(bands)) as ex:
        if shared:
            sh = shared_sigma(y, k, bands, pool=ex, _stats=_stats)
            tol = shared_tolerance(sh.sigma, k, eps)
            hand = kw.get("fidelity", "practical") != "paper"
            band_kw = [dict(kw, tolerance_override=tol,
                            **({"bound": b} if hand else {}))
                       for b in sh.band_bics]
        # pool threads do not inherit context: each band re-enters the
        # caller's span, so its phases and dispatch join the build's trace
        with obs.cpu_span("core.bands", bands=len(bands)):
            parent = obs.current_span()

            def build(i: int, r0: int, r1: int) -> SignalCoreset:
                with obs.attach(parent), \
                        obs.cpu_span("core.band", band=i, row0=r0, row1=r1):
                    return signal_coreset(y[r0:r1], k, eps, **band_kw[i])

            parts = list(ex.map(build, range(len(bands)), *zip(*bands)))
            # join the pool inside the span: no thread's exit may fall
            # between the build's phases
            ex.shutdown()
    offsets = [b[0] for b in bands]
    if shared:
        cs = certify_shared(parts, offsets, y.shape[0], sh.sigma)
    else:
        with obs.cpu_span("core.compose") as sp:
            cs = compose(parts, offsets, n_total=y.shape[0])
            sp.set_attr("blocks", cs.num_blocks)
    return recompress(cs) if recompress_result else cs


# ------------------------------------------------- batched Algorithm 5 eval
MESH_BACKEND = "pallas+shard_map"


def fitting_loss_batched(cs: SignalCoreset, seg_rects: np.ndarray,
                         seg_labels: np.ndarray, mesh=None,
                         data_axis: str = "data", backend: str | None = None,
                         interpret: bool | None = None):
    """Evaluate T candidate segmentations at once: seg_rects (T, K, 4),
    seg_labels (T, K).  Returns (T,).

    Without a mesh this is the dispatched ``repro.ops.fitting_loss_batched``
    (numpy oracle / jitted xla / batched Pallas kernel, by selection rules
    or the explicit ``backend=``).  With a mesh, blocks are sharded over
    ``data_axis`` via ``shard_map`` and every device runs the *batched
    Pallas kernel* on its own shard against all T trees, then ONE ``psum``
    folds the per-shard partial losses — the collective pattern the cluster
    scoring path rides (previously this branch pjit'ed the dense XLA ref,
    so on a pod the kernel never saw the mesh).  The dispatch profile
    records the hop under backend :data:`MESH_BACKEND` through the same
    hook the ops registry uses.
    """
    if mesh is None:
        from repro import ops
        return ops.fitting_loss_batched(cs, np.asarray(seg_rects),
                                        np.asarray(seg_labels),
                                        backend=backend)

    import time as _time

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.kernels.common import default_interpret
    from repro.kernels.fitting_loss.kernel import fitting_loss_batched_call
    from repro.obs import profile as _profile
    from repro.obs import span as _span

    if interpret is None:
        interpret = default_interpret()

    rects = jnp.asarray(cs.rects, jnp.float32)
    lab4 = jnp.asarray(cs.labels, jnp.float32)
    w4 = jnp.asarray(cs.weights, jnp.float32)
    sr = jnp.asarray(seg_rects, jnp.float32)
    sl = jnp.asarray(seg_labels, jnp.float32)
    B = rects.shape[0]
    T = sr.shape[0]
    shards = int(mesh.shape[data_axis])
    pad = (-B) % shards
    if pad:
        # zero-weight padding blocks contribute no loss
        rects = jnp.pad(rects, ((0, pad), (0, 0)))
        lab4 = jnp.pad(lab4, ((0, pad), (0, 0)))
        w4 = jnp.pad(w4, ((0, pad), (0, 0)))

    def _body(r, l4, wt, s_r, s_l):
        # per-shard (B/shards)-block slab through the fused Pallas kernel,
        # then the single collective of the whole dispatch
        part = fitting_loss_batched_call(r, l4, wt, s_r, s_l,
                                         interpret=interpret)
        return jax.lax.psum(part, axis_name=data_axis)

    spec = P(data_axis, None)
    # replication check off: it cannot see through the Pallas call
    f = jax.shard_map(_body, mesh=mesh,
                      in_specs=(spec, spec, spec, P(), P()),
                      out_specs=P(), check_vma=False)
    size = int(B) * int(T)
    t0 = _time.perf_counter()
    with _span("ops.dispatch", op="fitting_loss_batched",
               backend=MESH_BACKEND, size=size):
        with jax.set_mesh(mesh):
            out = np.asarray(jax.jit(f)(rects, lab4, w4, sr, sl))
    if _profile._HOOKS:
        _profile.record("fitting_loss_batched", MESH_BACKEND, size,
                        _time.perf_counter() - t0)
    return out
