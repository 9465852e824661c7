"""End-to-end distributed XCT reconstruction (the paper's system, in JAX).

``Reconstructor`` binds a partition plan to a TPU mesh and exposes
``project`` / ``backproject`` / ``reconstruct``.  The whole CG solve runs
inside one ``shard_map``: per-device blocked-ELL SpMM (Pallas kernel) ->
mixed-precision cast with adaptive normalization -> partial-data reduction
(direct / reduce-scatter / hierarchical / sparse footprint exchange /
hierarchical-sparse socket-deduplicated exchange) ->
CGNR update, with slice-minibatches software-pipelined so reductions overlap
the next minibatch's kernel (paper Fig. 8).

Mesh-axis roles follow the paper's optimal partitioning strategy
(Sec. III-A3): in-slice data parallelism (which communicates) lives on the
*fast* axes; batch parallelism over slices (which doesn't) on the slow ones.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..dist import Topology
from ..dist.collectives import sparse_exchange
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..obs.trace import span as obs_span
from ..resil import inject
from ..resil.errors import NonFiniteSolveError
from ..kernels.ops import (
    apply_operator,
    dma_issue_count,
    sort_segments_by_class,
    winmap_segments,
)
from .hilbert import hilbert_argsort  # noqa: F401  (re-export convenience)
from .partition import (
    Plan,
    build_hier_sparse_exchange,
    build_sparse_exchange,
    estimate_hier_sparse,
)
from .pipeline import pipelined_apply
from .precision import (
    adaptive_scale_cols,
    get_policy,
    qcast,
    quantize_block_vals,
)
from .solver import cgnr

__all__ = ["ReconConfig", "Reconstructor", "StagedSlab"]


@dataclasses.dataclass(frozen=True)
class StagedSlab:
    """A sinogram slab already packed, normalized and on device.

    Produced by :meth:`Reconstructor.stage_sino`; pass it to
    :meth:`Reconstructor.reconstruct` in place of the natural-order
    numpy slab to skip the host->device staging inside the solve.  The
    streaming driver stages slab ``i+1`` from its prefetch thread while
    slab ``i`` solves (the Fig. 8 overlap applied to the jit argument
    transfer) -- results are bit-identical either way because the same
    pack/scale/transfer runs, just earlier.

    A slab of any slice count is staged: its columns are padded with
    zeros up to whole fused passes (a multiple of ``n_batch * fuse``),
    so a short last slab solves through the same program as a full
    one.  ``n_slices`` is the real count; the ``pad_slices`` zero lanes
    follow it, each with scale 1, and ``reconstruct`` cuts them off.
    """

    y: object  # [sino_pad, Y + pad] f32 device array, pre-scaled
    scale: np.ndarray  # [Y + pad] power-of-two per-slice normalization
    n_slices: int  # Y, the real slices
    pad_slices: int  # zero lanes after them


@dataclasses.dataclass(frozen=True)
class ReconConfig:
    precision: str = "mixed"  # paper ladder: double|single|half|mixed
    #   (+bf16 variants, +q8/fp8 quantized-operator tiers)
    comm_mode: str = "hier"  # direct | rs | hier | sparse | hier-sparse
    wire: str = "native"  # hier-sparse slow-axis wire: native | q8
    fuse: int = 16  # paper's minibatch size (FFACTOR)
    overlap: bool = True  # Fig. 8 pipelining
    use_ref: bool = False  # oracle instead of Pallas kernel
    interpret: bool | None = None  # Pallas interpret (auto off-TPU)
    staging: str = "fused"  # in-kernel window staging | legacy "gather"
    dma: str = "coalesced"  # run-length window DMAs | "per_row" A/B
    # per-call SMEM budget for the kernel's chunked scalar prefetch
    # (None = kernels.xct_spmm.SMEM_BUDGET)
    smem_budget: int | None = None
    # [deprecated] only the legacy gather path chunks its staging
    # transient; the fused kernel's staging lives in VMEM.
    blocks_per_call: int | None = None

    @classmethod
    def tuned(cls, passport=None, *, tune_dir=None, **overrides):
        """Build a config from a tuning passport (``repro.tune``).

        Resolution: an explicit ``passport`` wins; else the passport
        for THIS machine's hardware fingerprint is looked up under
        ``tune_dir`` (missing or unusable -> stock defaults, never an
        error); ``overrides`` beat passport knobs either way.  Only the
        knobs this dataclass owns are consumed (``precision``,
        ``comm_mode``, ``wire``, ``fuse``, ``dma``) -- partition-level knobs live
        in the passport for ``build_plan`` callers to apply.
        """
        if passport is None and tune_dir is not None:
            from ..tune.passport import resolve_passport

            passport = resolve_passport(tune_dir)
        kw = {}
        if passport is not None:
            for field in ("precision", "comm_mode", "wire", "fuse", "dma"):
                if field in passport.knobs:
                    kw[field] = passport.knobs[field]
        kw.update(overrides)
        return cls(**kw)


class Reconstructor:
    """Distributed iterative reconstruction bound to a mesh topology.

    Args:
      plan: partition plan (``core.partition.build_plan``).
      topology: ``dist.Topology`` naming the communicating (data) and
        batch mesh axes -- ``Topology.from_mesh(mesh, data_axes=...,
        batch_axes=...)``.  The data levels' size product must equal
        ``plan.cfg.n_data``.
      mesh: [deprecated path] JAX mesh; default = 1-device mesh (plan
        must have n_data == 1).  Ignored when ``topology`` is given.
      data_axes, batch_axes: [deprecated] loose axis tuples; pass a
        ``topology`` instead (see docs/dist_api.md).
      cfg: runtime configuration.
    """

    def __init__(
        self,
        plan: Plan,
        mesh=None,
        data_axes=None,
        batch_axes=None,
        cfg: ReconConfig = ReconConfig(),
        abstract: bool = False,
        topology: Topology | None = None,
    ):
        if topology is None:
            if data_axes is not None or batch_axes is not None:
                warnings.warn(
                    "Reconstructor(data_axes=..., batch_axes=...) is "
                    "deprecated; pass topology=Topology.from_mesh(mesh, "
                    "data_axes=..., batch_axes=...) instead",
                    DeprecationWarning,
                    stacklevel=2,
                )
            if mesh is None:
                mesh = jax.make_mesh(
                    (1, 1), ("data", "model"), devices=jax.devices()[:1]
                )
            topology = Topology.from_mesh(
                mesh,
                data_axes=("model",) if data_axes is None
                else tuple(data_axes),
                batch_axes=("data",) if batch_axes is None
                else tuple(batch_axes),
            )
        elif mesh is not None or data_axes is not None \
                or batch_axes is not None:
            raise ValueError(
                "pass either topology= or the deprecated "
                "mesh/data_axes/batch_axes, not both"
            )
        if topology.mesh is None:
            raise ValueError(
                "Reconstructor needs a mesh-bound topology "
                "(Topology.from_mesh)"
            )
        self.plan = plan
        self.topology = topology
        self.mesh = mesh = topology.mesh
        self.cfg = cfg
        if cfg.blocks_per_call is not None:
            warnings.warn(
                "ReconConfig.blocks_per_call is deprecated: the default "
                "fused staging has no HBM transient to chunk; it only "
                'affects the legacy staging="gather" path',
                DeprecationWarning,
                stacklevel=2,
            )
        self.abstract = abstract
        self.data_axes = topology.data_axes
        self.batch_axes = topology.batch_axes
        self.policy = get_policy(cfg.precision)
        if cfg.wire not in ("native", "q8"):
            raise ValueError(
                f"unknown wire {cfg.wire!r}; one of ('native', 'q8')"
            )
        if cfg.wire == "q8" and cfg.comm_mode != "hier-sparse":
            raise ValueError(
                "wire='q8' compresses the hier-sparse slow-axis hop; "
                f"comm_mode={cfg.comm_mode!r} has no such hop (use "
                "comm_mode='hier-sparse' or wire='native')"
            )
        self.comm_plan = topology.plan(cfg.comm_mode)
        if topology.n_data != plan.cfg.n_data:
            raise ValueError(
                f"plan has P_d={plan.cfg.n_data} but data axes "
                f"{self.data_axes} have size {topology.n_data}"
            )
        fast = topology.levels[0].size if topology.levels else 1
        if plan.cfg.socket not in (1, fast):
            warnings.warn(
                f"plan was laid out for socket={plan.cfg.socket} but the "
                f"topology's fast level is {fast}-wide; the hier-sparse "
                "dedup will not see consecutive chunks per socket",
                stacklevel=2,
            )
        self.n_batch = topology.n_batch
        self._rank_rows = None  # lazy inverse row permutation
        self._rank_cols = None
        self._fns: dict = {}
        self._arrays = self._device_arrays()
        # what each solve transfers of the operator: the host arrays
        # (a device-resident array moves nothing), reckoned once here
        self._operator_h2d = sum(
            a.nbytes for a in self._arrays.values()
            if isinstance(a, np.ndarray)
        )
        self._kernel_counts = {
            "dma_segments": self._window_dmas(),
            "grid_steps": self._grid_steps(),
        }

    # ------------------------------------------------------------------ #
    # data movement helpers (host side)
    # ------------------------------------------------------------------ #
    @property
    def tomo_pad(self) -> int:
        return self.plan.proj.n_cols_pad

    @property
    def sino_pad(self) -> int:
        return self.plan.proj.n_rows_pad

    def pack_tomo(self, x_nat):
        """[n_vox, Y] natural order -> [tomo_pad, Y] stored (device-major
        Hilbert) order; Hilbert chunks land on their owning device slot
        per the plan's socket-aware layout (identity when socket == 1)."""
        n = self.plan.geo.n_vox
        out = np.zeros((self.tomo_pad, x_nat.shape[1]), np.float32)
        pos = self.plan.col_pos
        dst = slice(None, n) if pos is None else pos[:n]
        out[dst] = np.asarray(x_nat)[self.plan.col_perm]
        return out

    def unpack_tomo(self, x_curve):
        g = self.plan.geo
        if self._rank_cols is None:
            pos = self.plan.col_pos
            stored = (
                np.arange(g.n_vox) if pos is None else pos[: g.n_vox]
            )
            rank = np.empty(g.n_vox, np.int64)
            rank[self.plan.col_perm] = stored
            self._rank_cols = rank
        return np.asarray(x_curve)[self._rank_cols]

    def pack_sino(self, y_nat):
        n = self.plan.geo.n_rays
        out = np.zeros((self.sino_pad, y_nat.shape[1]), np.float32)
        pos = self.plan.row_pos
        dst = slice(None, n) if pos is None else pos[:n]
        out[dst] = np.asarray(y_nat)[self.plan.row_perm]
        return out

    def unpack_sino(self, y_curve):
        g = self.plan.geo
        if self._rank_rows is None:
            pos = self.plan.row_pos
            stored = (
                np.arange(g.n_rays) if pos is None else pos[: g.n_rays]
            )
            rank = np.empty(g.n_rays, np.int64)
            rank[self.plan.row_perm] = stored
            self._rank_rows = rank
        return np.asarray(y_curve)[self._rank_rows]

    # ------------------------------------------------------------------ #
    # device arrays
    # ------------------------------------------------------------------ #
    def _device_arrays(self):
        pol = self.policy
        plan = self.plan
        mode = self.cfg.comm_mode
        fast = self.topology.levels[0].size if self.topology.levels else 1
        n_slow = max(1, self.topology.n_data // fast)
        self._socket_rows: dict = {}  # static W per operator (hier-sparse)
        # per-block shapes of the DMA descriptor tables: they rest flat
        # ([P, B, S*NSEG*3], [P, B, S*(NCLS+1)]) because device memory
        # pads a short minor dimension (the 3 of {src, dst, len}) to a
        # whole 128-lane tile, and are reshaped back at the kernel call
        self._table_shapes: dict = {}
        arrs = {}
        for name, op in (("proj", plan.proj), ("back", plan.back)):
            if self.abstract:
                sds = jax.ShapeDtypeStruct
                arrs[f"{name}_inds"] = sds(op.inds.shape, jnp.int16)
                arrs[f"{name}_vals"] = sds(op.vals.shape, pol.vals_dtype)
                if pol.quantized:
                    arrs[f"{name}_vscale"] = sds(
                        op.vals.shape[:3], jnp.int32
                    )
                arrs[f"{name}_winmap"] = sds(op.winmap.shape, jnp.int32)
                buf = op.winmap.shape[-1]
                if op.winsegs is not None and op.segoff is not None:
                    segs_shape = op.winsegs.shape
                    off_shape = op.segoff.shape
                else:
                    # older pickled plans: real winmap, no tables yet
                    segs, off = sort_segments_by_class(
                        winmap_segments(op.winmap), buf
                    )
                    segs_shape, off_shape = segs.shape, off.shape
                self._table_shapes[name] = (segs_shape[2:], off_shape[2:])
                arrs[f"{name}_winsegs"] = sds(
                    (*segs_shape[:2], math.prod(segs_shape[2:])), jnp.int32
                )
                arrs[f"{name}_segoff"] = sds(
                    (*off_shape[:2], math.prod(off_shape[2:])), jnp.int32
                )
                arrs[f"{name}_row_map"] = sds(
                    op.row_map.shape, jnp.int32
                )
                p = op.inds.shape[0]
                if mode == "sparse":
                    v = getattr(op, "est_v", 8)
                    arrs[f"{name}_send"] = sds((p, p, v), jnp.int32)
                    arrs[f"{name}_recv"] = sds((p, p, v), jnp.int32)
                elif mode == "hier-sparse":
                    w, v2 = estimate_hier_sparse(op, fast, n_slow)
                    self._socket_rows[name] = w
                    arrs[f"{name}_smap"] = sds(
                        (p, op.flat_rows), jnp.int32
                    )
                    arrs[f"{name}_send"] = sds((p, n_slow, v2), jnp.int32)
                    arrs[f"{name}_recv"] = sds((p, n_slow, v2), jnp.int32)
                continue
            arrs[f"{name}_inds"] = op.inds
            if pol.quantized:
                # pack once at bind time: int8/fp8 values + per-(block,
                # stage) power-of-two dequant exponents the kernel
                # applies inline (core.precision.quantize_block_vals)
                q, exp = quantize_block_vals(op.vals, pol.vals_dtype)
                arrs[f"{name}_vals"] = np.asarray(q)
                arrs[f"{name}_vscale"] = np.asarray(exp)
            else:
                arrs[f"{name}_vals"] = op.vals.astype(pol.storage)
            arrs[f"{name}_winmap"] = op.winmap
            if op.winsegs is not None and op.segoff is not None:
                segs, off = op.winsegs, op.segoff
            else:  # older pickled plans: build both tables now
                segs, off = sort_segments_by_class(
                    winmap_segments(op.winmap), op.winmap.shape[-1]
                )
            self._table_shapes[name] = (segs.shape[2:], off.shape[2:])
            arrs[f"{name}_winsegs"] = segs.reshape(*segs.shape[:2], -1)
            arrs[f"{name}_segoff"] = off.reshape(*off.shape[:2], -1)
            arrs[f"{name}_row_map"] = op.row_map
            if mode == "sparse":
                send, recv, _ = build_sparse_exchange(op)
                arrs[f"{name}_send"] = send
                arrs[f"{name}_recv"] = recv
            elif mode == "hier-sparse":
                smap, send, recv, w, _ = build_hier_sparse_exchange(
                    op, fast
                )
                self._socket_rows[name] = w
                arrs[f"{name}_smap"] = smap
                arrs[f"{name}_send"] = send
                arrs[f"{name}_recv"] = recv
        return arrs

    def _window_dmas(self) -> dict:
        """Window DMAs the kernel issues per apply of each operator, over
        the devices of one batch group: one a real segment of its
        coalesced table.  Only the default fused, coalesced path reads
        the table; the other arms and abstract plans count none."""
        cfg = self.cfg
        if (self.abstract or cfg.use_ref or cfg.staging != "fused"
                or cfg.dma != "coalesced"):
            return {}
        return {
            name: dma_issue_count(
                self._arrays[f"{name}_winsegs"].reshape(-1, 3)
            )
            for name in ("proj", "back")
        }

    def _grid_steps(self) -> dict:
        """Grid steps the kernel runs per apply of each operator, over
        the devices of one batch group: one a row block on the fused
        path, whose step loops over the block's stages; one a (row
        block, stage) on the legacy gather arm.  The reference and
        abstract plans count none."""
        cfg = self.cfg
        if self.abstract or cfg.use_ref:
            return {}
        lead = 2 if cfg.staging == "fused" else 3  # [P, B] or [P, B, S]
        return {
            name: math.prod(self._arrays[f"{name}_inds"].shape[:lead])
            for name in ("proj", "back")
        }

    def lower_cg(self, y_slices: int, iters: int):
        """Lower+compile the CG step with abstract inputs (dry-run)."""
        sds = jax.ShapeDtypeStruct
        y = sds((self.sino_pad, y_slices), jnp.float32)
        x0 = sds((self.tomo_pad, y_slices), jnp.float32)
        fn = self._get_fn("cg", iters)
        lowered = fn.lower(self._arrays, y, x0)
        return lowered, lowered.compile()

    # ------------------------------------------------------------------ #
    # per-device compute
    # ------------------------------------------------------------------ #
    def _make_ops(self, a):
        """Closures (project, backproject, dot_rows) for shard-local data."""
        cfg, pol = self.cfg, self.policy
        daxes = self.data_axes
        plan = self.plan

        def one_operator(prefix, rows_out):
            inds = a[f"{prefix}_inds"][0]
            vals = a[f"{prefix}_vals"][0]
            vscale = (
                a[f"{prefix}_vscale"][0] if pol.quantized else None
            )
            winmap = a[f"{prefix}_winmap"][0]
            segs_shape, off_shape = self._table_shapes[prefix]
            winsegs = a[f"{prefix}_winsegs"][0].reshape(
                -1, *segs_shape
            )
            segoff = a[f"{prefix}_segoff"][0].reshape(-1, *off_shape)
            row_map = a[f"{prefix}_row_map"][0]
            n_rows_pad = rows_out * math.prod(
                self.mesh.shape[x] for x in daxes
            )

            def kernel(x_f):
                return apply_operator(
                    inds,
                    vals,
                    winmap,
                    x_f,
                    storage_dtype=pol.storage,
                    compute_dtype=pol.compute,
                    use_ref=cfg.use_ref,
                    interpret=cfg.interpret,
                    staging=cfg.staging,
                    dma=cfg.dma,
                    winsegs=winsegs,
                    segoff=segoff,
                    smem_budget=cfg.smem_budget,
                    blocks_per_call=cfg.blocks_per_call,
                    scales=vscale,
                    op=prefix,
                )

            comm_plan = self.comm_plan

            def reduce(band):
                bandc, inv = qcast(
                    band,
                    pol.comm,
                    adaptive=pol.adaptive,
                    axis_name=daxes,
                )
                if cfg.comm_mode in ("sparse", "hier-sparse"):
                    hier = cfg.comm_mode == "hier-sparse"
                    chunk = sparse_exchange(
                        bandc,
                        a[f"{prefix}_send"][0],
                        a[f"{prefix}_recv"][0],
                        self.topology,
                        rows_out,
                        socket_map=(
                            a[f"{prefix}_smap"][0] if hier else None
                        ),
                        socket_rows=(
                            self._socket_rows[prefix] if hier else None
                        ),
                        wire=cfg.wire,
                    )
                else:
                    # scatter-ADD: split rows (virtual-row packing) may
                    # map several band slots onto one global row
                    idx = row_map.reshape(-1)
                    full = (
                        jnp.zeros((n_rows_pad, band.shape[-1]), bandc.dtype)
                        .at[idx]
                        .add(bandc, mode="drop")
                    )
                    chunk = comm_plan.reduce_partials(full)
                return chunk.astype(jnp.float32) * inv

            narrow = (
                pol.storage_bytes < 4
                or jnp.dtype(pol.compute).itemsize < 4
            )

            def apply(x_all):
                inv = None
                if narrow:
                    # Paper III-C1: renormalize the evolving iterate per
                    # slice before every (back)projection so the fp16
                    # accumulation never under/overflows.
                    s = adaptive_scale_cols(x_all, 1.0, daxes)
                    x_all = (
                        x_all.astype(jnp.float32) * s
                    ).astype(pol.storage)
                    inv = 1.0 / s
                out = pipelined_apply(
                    kernel, reduce, x_all, cfg.fuse, overlap=cfg.overlap
                )
                return out if inv is None else out * inv

            return apply

        project = one_operator("proj", plan.proj.rows_per_dev)
        backproject = one_operator("back", plan.back.rows_per_dev)

        def dot_rows(u, v):
            # Scalar reductions always in f32: a half-mode dot over 1e6+
            # entries overflows f16's 65504 range (the paper's half mode
            # relies on its normalized beamline data; we normalize inputs
            # too -- see reconstruct() -- and keep the reduction wide).
            s = jnp.sum(
                u.astype(jnp.float32) * v.astype(jnp.float32), axis=0
            )
            return jax.lax.psum(s, daxes)

        return project, backproject, dot_rows

    # ------------------------------------------------------------------ #
    # jitted entry points
    # ------------------------------------------------------------------ #
    def _specs(self):
        d = P(self.data_axes)
        op_names = ["inds", "vals", "winmap", "winsegs", "segoff",
                    "row_map"]
        if self.policy.quantized:
            op_names += ["vscale"]
        if self.cfg.comm_mode == "sparse":
            op_names += ["send", "recv"]
        elif self.cfg.comm_mode == "hier-sparse":
            op_names += ["send", "recv", "smap"]
        arr_specs = {
            f"{pre}_{nm}": d for pre in ("proj", "back") for nm in op_names
        }
        vec = P(self.data_axes, self.batch_axes or None)
        return arr_specs, vec

    def _get_fn(self, kind: str, iters: int = 0):
        key = (kind, iters)
        if key in self._fns:
            return self._fns[key]
        arr_specs, vec = self._specs()
        pol = self.policy

        if kind in ("project", "backproject"):

            def fn(a, x):
                proj, back, _ = self._make_ops(a)
                op = proj if kind == "project" else back
                return op(x.astype(pol.storage)).astype(jnp.float32)

            out_specs = vec
        elif kind == "cg":

            def fn(a, y, x0):
                proj, back, dot = self._make_ops(a)
                x, res = cgnr(
                    proj,
                    back,
                    y,
                    x0,
                    iters,
                    dot,
                    compute_dtype=pol.compute,
                    storage_dtype=pol.storage,
                )
                return x.astype(jnp.float32), res.astype(jnp.float32)

            out_specs = (vec, P(None, self.batch_axes or None))
        else:
            raise ValueError(kind)

        in_specs = (arr_specs,) + (
            (vec,) if kind != "cg" else (vec, vec)
        )
        mapped = jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )
        jitted = jax.jit(mapped)
        self._fns[key] = jitted
        return jitted

    # ------------------------------------------------------------------ #
    # public API (natural-order numpy in/out)
    # ------------------------------------------------------------------ #
    def _pad_slices(self, y: int) -> int:
        """Zero lanes that fill ``y`` slices up to whole fused passes: the
        solve runs on a multiple of ``n_batch * fuse`` slices."""
        return -y % (self.n_batch * self.cfg.fuse)

    def project(self, x_nat):
        """[n_vox, Y] -> [n_rays, Y] forward projection."""
        y = x_nat.shape[1]
        x = _pad_cols(self.pack_tomo(x_nat), self._pad_slices(y))
        out = self._get_fn("project")(self._arrays, x)
        return self.unpack_sino(np.asarray(out)[:, :y])

    def backproject(self, y_nat):
        """[n_rays, Y] -> [n_vox, Y] back projection (A^T)."""
        y = y_nat.shape[1]
        sino = _pad_cols(self.pack_sino(y_nat), self._pad_slices(y))
        out = self._get_fn("backproject")(self._arrays, sino)
        return self.unpack_tomo(np.asarray(out)[:, :y])

    def stage_sino(self, sino_nat) -> StagedSlab:
        """Pack + normalize + upload one sinogram slab (host -> device).

        The host->device half of :meth:`reconstruct`, split out so a
        prefetch thread can run it for slab ``i+1`` while slab ``i``
        solves (``stream.driver`` wires this through
        ``scheduler.Prefetcher``'s ``stage=``).  Blocks until the
        transfer lands so the caller's timing is honest.  Any slice count
        is staged; the padding to whole fused passes is appended after
        packing and scaling (see :class:`StagedSlab`).
        """
        n = int(sino_nat.shape[1])
        pad = self._pad_slices(n)
        with obs_span("recon/stage", slices=n, pad_slices=pad) as sp:
            y = self.pack_sino(sino_nat)
            m = np.abs(y).max(axis=0)
            # target 1.0: keeps every CG vector (and the fp16 CG
            # scalars) O(n * K) at most, inside half range for any
            # practical geometry
            scale = np.exp2(
                np.round(np.log2(1.0 / np.maximum(m, 1e-30)))
            ).astype(np.float32)
            _, vec = self._specs()
            y = _pad_cols(y * scale, pad)
            scale = np.concatenate([scale, np.ones(pad, np.float32)])
            _count_h2d(sp, "sino", y.nbytes)
            y_dev = jax.device_put(
                y, jax.sharding.NamedSharding(self.mesh, vec)
            )
            jax.block_until_ready(y_dev)
        return StagedSlab(y=y_dev, scale=scale, n_slices=n, pad_slices=pad)

    def reconstruct(self, sino_nat, iters: int = 30, x0_nat=None):
        """CGNR solve; returns ``(x [n_vox, Y], resnorms [iters, Y])``.

        Inputs are adaptively normalized per slice (power-of-two factor
        steering max|y| to ~256, paper Sec. III-C1) so narrow-precision
        iterates stay in range; the solution scales back exactly.
        ``sino_nat`` may be a pre-staged :class:`StagedSlab` (see
        :meth:`stage_sino`); the math is identical either way.  The zero
        lanes that pad a short slab to whole fused passes are cut off
        before unpacking, scaling back and the finiteness check.

        Raises :class:`~repro.resil.errors.NonFiniteSolveError` when
        the solution contains NaN/Inf (a blown-up narrow-precision
        solve) -- the streaming driver's retry/escalate/quarantine
        hook.
        """
        staged = (
            sino_nat
            if isinstance(sino_nat, StagedSlab)
            else self.stage_sino(sino_nat)
        )
        scale = staged.scale
        n, pad = staged.n_slices, staged.pad_slices
        lanes = n + pad
        x0 = (
            _pad_cols(self.pack_tomo(x0_nat), pad) * scale
            if x0_nat is not None
            else np.zeros((self.tomo_pad, lanes), np.float32)
        )
        with obs_span("recon/solve", iters=iters, slices=n) as sp:
            # argument transfer and dispatch; the rest of the solve
            # span is the fenced wait on the device
            with obs_span("recon/dispatch", pad_slices=pad) as sp_call:
                _count_h2d(sp_call, "operator", self._operator_h2d)
                _count_h2d(sp_call, "x0", x0.nbytes)
                obs_metrics.inc("padded_slices_total", pad)
                # CGNR: the initial A/A^T pair + one per iteration, for
                # every fused minibatch the solve runs, padding included
                applies = (iters + 1) * (lanes // self.cfg.fuse)
                for attr, per_op in self._kernel_counts.items():
                    for op, per_apply in per_op.items():
                        _count_kernel(sp_call, attr, op, per_apply * applies)
                x, res = self._get_fn("cg", iters)(
                    self._arrays, staged.y, x0
                )
            sp.fence(x)  # async dispatch must not end the span early
        self._emit_exchange(iters, n)
        with obs_span("recon/unpack", slices=n):
            obs_metrics.inc("d2h_bytes_total", x.nbytes, what="volume")
            obs_metrics.inc("d2h_bytes_total", res.nbytes, what="resnorm")
            scale = scale[:n]
            x_nat = self.unpack_tomo(np.asarray(x)[:, :n]) / scale
            # the resilience guard: a narrow-precision solve that blew
            # up (or an injected nonfinite fault) surfaces as a typed
            # error the streaming driver can retry / escalate one
            # precision rung / quarantine, instead of NaNs landing
            # silently in the volume
            x_nat = inject.mutate(
                "recon/solve", x_nat, ctx={"precision": self.cfg.precision}
            )
            if not np.isfinite(x_nat).all():
                n_bad = int(x_nat.size - np.isfinite(x_nat).sum())
                raise NonFiniteSolveError(
                    f"solve produced {n_bad} non-finite value(s) over "
                    f"{n} slices (precision={self.cfg.precision})"
                )
            res_nat = np.asarray(res)[:, :n] / scale
        return x_nat, res_nat

    def _emit_exchange(self, iters: int, n_slices: int):
        """Annotate a finished solve with its modeled wire traffic.

        The exchanges themselves run inside the jitted shard_map --
        host spans cannot time them -- so when tracing is on we emit a
        ``recon/exchange`` instant carrying the *modeled* per-link
        bytes of the whole solve (``launch.xct_perf.comm_volume`` per
        fused minibatch, a short slab's padded one counted whole, x
        ``iters + 1`` operator applications, the same pricing the
        autotuner and ``obs.drift`` use) and bump the
        ``comm_bytes_total{link=}`` counters.
        """
        tracer = obs_trace.get_tracer()
        if not tracer.enabled:
            return
        per_mini = getattr(self, "_obs_traffic", None)
        if per_mini is None:
            from ..launch.xct_perf import comm_volume

            per_mini = self._obs_traffic = comm_volume(
                self.plan, self.cfg.comm_mode, self.cfg.fuse,
                self.policy.comm_bytes, self.topology,
                wire=self.cfg.wire,
            )
        minis = -(-n_slices // (self.n_batch * self.cfg.fuse))
        apps = iters + 1  # CGNR: initial A/A^T pair + one per iteration
        scale = minis * apps
        tracer.instant(
            "recon/exchange",
            ici_bytes=per_mini["ici"] * scale,
            dci_bytes=per_mini["dci"] * scale,
            iters=iters,
            slices=n_slices,
        )
        obs_metrics.inc(
            "comm_bytes_total", per_mini["ici"] * scale, link="ici"
        )
        obs_metrics.inc(
            "comm_bytes_total", per_mini["dci"] * scale, link="dci"
        )


def _pad_cols(a: np.ndarray, pad: int) -> np.ndarray:
    """``a`` with ``pad`` zero columns appended (``a`` itself if none)."""
    return np.pad(a, ((0, 0), (0, pad))) if pad else a


def _count_kernel(sp, attr: str, op: str, n: int):
    """Count what a solve's kernel calls do for one operator -- window
    DMAs (``attr="dma_segments"``) or grid steps (``"grid_steps"``) --
    in ``spmm_<attr>_total{op}`` and add it to the span that dispatches
    the solve (its ``<attr>``)."""
    obs_metrics.inc(f"spmm_{attr}_total", n, op=op)
    sp.attrs[attr] = sp.attrs.get(attr, 0) + n


def _count_h2d(sp, what: str, nbytes: int):
    """Count host->device bytes a call transfers (``h2d_bytes_total``)
    and add them to the span that transfers them (its ``h2d_bytes``)."""
    obs_metrics.inc("h2d_bytes_total", nbytes, what=what)
    sp.attrs["h2d_bytes"] = sp.attrs.get("h2d_bytes", 0) + nbytes
