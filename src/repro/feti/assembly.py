"""Cluster preprocessing: numerical factorization + explicit SC assembly,
batched over the subdomains of a cluster (paper §2.2 "preprocessing").

All subdomains of the structured decomposition share one local topology, so
they share the fill-reducing permutation, the symbolic block fill mask and
the (envelope) stepped metadata — the whole cluster preprocesses in ONE
compiled XLA program with a leading subdomain axis. This replaces the
paper's 16-CUDA-streams subdomain loop with the TPU-idiomatic batched form.

Since the stage-graph redesign the preprocessor is organized around
:class:`repro.core.stages.StageGraph`: every Schur assembly stage — the
dual operator F̃ = (L⁻¹B̃ᵀ)ᵀ(L⁻¹B̃ᵀ) and (with the Dirichlet
preconditioner) the primal boundary S_b = K_bb − K_bi K_ii⁻¹ K_ib — is
declared as a :class:`~repro.core.stages.StageSpec` and planned JOINTLY
under one cache key, then executed by one compiled prep. When the
boundary/interior split aligns with the row ordering the graph dedupes the
interior factorization: the dual rows are reordered ``split.dperm`` so the
dual factor's leading (n_i, n_i) principal block IS the Cholesky factor of
the unregularized K_ii, and the Dirichlet stage reuses it instead of
factorizing its own copy (docs/stage_graph.md §Factor sharing).

Pass ``FetiConfig(mesh=...)`` (a ``("data",)`` mesh, see
:func:`repro.launch.mesh.make_feti_mesh`) to shard the subdomain axis over
devices — the multi-node story. Preprocessing then relabels local
multipliers into each subdomain's stepped column order host-side (the
``col_perm=None`` assembler path), pads the cluster to a multiple of the
mesh size, and factorizes + assembles under ``shard_map`` so every device
owns its slice of subdomains end-to-end; :mod:`repro.feti.sharded`
documents the scheme. ``mesh=None`` keeps the single-device behavior
bit-for-bit.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    SchurAssemblyConfig,
    build_stepped_meta,
    make_assembler,
    shared_envelope,
)
from repro.core import precision
from repro.core.autotune import Plan, pattern_fingerprint
from repro.core.stages import GraphPlan, StageGraph, StageSpec
from repro.core.stepped import SteppedMeta
from repro.fem.decomposition import FetiProblem
from repro.fem.meshgen import structured_mesh
from repro.feti import dirichlet as dirlib
from repro.feti import sharded as shlib
from repro.feti import programs as proglib
from repro.feti.config import FetiConfig, _coerce_config, as_feti_config
from repro.obs.trace import current_tracer
from repro.sparse import (
    block_pattern,
    block_symbolic_cholesky,
    matrix_pattern_from_elems,
    node_ordering,
)
from repro.sparse.cholesky import block_cholesky
from repro.sparse.packed import (
    PackedBlockIndex,
    PackedBlocks,
    block_cholesky_packed,
)

__all__ = ["ClusterState", "preprocess_cluster", "batched_assemble",
           "expand_node_perm", "expand_node_pattern", "host_stacks",
           "bt_pattern"]


def expand_node_perm(node_perm: np.ndarray, ndpn: int) -> np.ndarray:
    """Expand a node permutation to node-blocked DOFs (identity for
    ndpn=1): each node's ndpn components move together, staying adjacent."""
    if ndpn == 1:
        return node_perm
    return (node_perm[:, None] * ndpn
            + np.arange(ndpn, dtype=node_perm.dtype)).reshape(-1)


def expand_node_pattern(npat: np.ndarray, ndpn: int) -> np.ndarray:
    """Expand a node adjacency pattern to node-blocked DOFs: every entry
    becomes a dense (ndpn, ndpn) block (identity for ndpn=1). The one
    definition shared by the preprocessor, the dry-run planner and the
    benchmarks, so their symbolic layouts can never diverge."""
    if ndpn == 1:
        return npat
    return np.kron(npat, np.ones((ndpn, ndpn), dtype=bool))


@dataclasses.dataclass
class ClusterState:
    """Everything the solution phase needs, stacked over subdomains.

    Stage outputs are keyed by stage name: ``outputs()["dual"]`` is the
    explicit SC stack ``F``, ``outputs()["dirichlet"]`` the boundary-Schur
    stack ``Sb``; ``stages`` carries each stage's resolved config,
    metadata and fill mask (:class:`repro.core.stages.ResolvedStage`) and
    ``graph_plan`` the joint autotuner result when ``schur="auto"``.

    When ``mesh`` is set, the subdomain-stacked device arrays are padded to
    a multiple of the mesh size, sharded over its ``data`` axis, and hold
    *relabeled* multiplier columns (each subdomain's stepped order — see
    :mod:`repro.feti.sharded`); ``lambda_ids`` is relabeled consistently so
    λ-space semantics are unchanged.
    """

    problem: FetiProblem
    cfg: SchurAssemblyConfig
    plan: Optional[Plan]  # autotuner plan when cfg was "auto", else None
    env: SteppedMeta  # shared stepped envelope (identity column perm)
    block_mask: np.ndarray  # factor block fill mask (shared)
    node_perm: np.ndarray  # fill-reducing row permutation (shared); equals
    #                        split.dperm when the interior factor is shared
    index: PackedBlockIndex  # packed block layout derived from block_mask
    # device arrays, leading axis = subdomain:
    # (S, n, n) Cholesky factors of permuted K_reg, or the packed
    # (S, n_blocks, bs, bs) stack when cfg.storage == "packed"
    L: Union[jax.Array, PackedBlocks]
    Btp: jax.Array  # (S, n, m_max) row-permuted B̃ᵀ (factor order)
    K: PackedBlocks  # packed permuted unregularized K (lumped
    #                  preconditioner); no dense (S, n, n) K is kept
    F: Optional[jax.Array]  # (S, m_max, m_max) explicit SC, or None (implicit)
    f: jax.Array  # (S, n) loads (original node order)
    fp: jax.Array  # (S, n) loads (factor order)
    lambda_ids: jax.Array  # (S, m_max) global multiplier ids (pad=n_lambda)
    col_perm: jax.Array  # (S_real, m_max) stepped column perm per subdomain
    inv_col_perm: jax.Array  # (S_real, m_max)
    R: jax.Array  # (S, n, k) orthonormal kernel bases, original DOF order
    #              (k = 1 heat constant; 3/6 elasticity rigid-body modes)
    # ---- mixed precision (ISSUE 9) ----
    # f64 packed regularized permuted K — the matrix the reduced-precision
    # factor approximates; kept ONLY when refine_steps > 0, it closes the
    # iterative-refinement loop (operator.solve_with_factor_refined)
    Kreg: Optional[PackedBlocks] = None
    refine_steps: int = 0  # resolved FetiConfig.refine (0 for f64 stacks)
    mesh: Optional[jax.sharding.Mesh] = None  # set => stacks sharded over it
    n_real: Optional[int] = None  # subdomain count before mesh padding
    relabeled: bool = False  # multiplier columns in stepped (relabeled) order
    # the compiled preprocessor, for the multi-step regime: new values,
    # same pattern, zero recompiles. Kp is the packed (S, n_blocks, bs, bs)
    # regularized K stack (host_stacks). Signature depends on the stage set:
    #   (Kp, Btp) -> (L, F)                          dual only
    #   (Kp, Btp, Kd, Zb) -> (L, F, Sb)              + dirichlet
    #   (Kp, Btp, Kbb, Zb) -> (L, F, Sb)             + dirichlet, shared
    #                                                  interior factor
    prep: Optional[Callable] = None
    # the structure's programs (repro.feti.programs): prep and the
    # solver's, shared with every cluster of the same structure
    programs: Optional[proglib.StructurePrograms] = None
    # ---- Dirichlet preconditioner stage (preconditioner="dirichlet") ----
    split: Optional[dirlib.BoundaryInteriorSplit] = None
    Sb: Optional[jax.Array] = None  # (S, n_b, n_b) primal boundary SCs
    Btb: Optional[jax.Array] = None  # (S, n_b, m_max) boundary rows of B̃ᵀ
    dirichlet_cfg: Optional[SchurAssemblyConfig] = None
    dirichlet_plan: Optional[Plan] = None  # when cfg was "auto", else None
    dirichlet_env: Optional[SteppedMeta] = None  # K_ib stepped metadata
    dirichlet_mask: Optional[np.ndarray] = None  # interior block fill mask
    # ---- stage graph (redesign) ----
    stages: Optional[dict] = None  # stage name -> ResolvedStage
    graph_plan: Optional[GraphPlan] = None  # joint plan when "auto"
    shared_factor: bool = False  # dirichlet reuses the dual interior factor

    @property
    def n_lambda(self) -> int:
        return self.problem.n_lambda

    @property
    def S(self) -> int:
        """Stacked subdomain count (including any mesh padding)."""
        L = self.L
        return (L.values if isinstance(L, PackedBlocks) else L).shape[0]

    @property
    def S_real(self) -> int:
        """Actual subdomain count (excluding mesh padding)."""
        return self.n_real if self.n_real is not None else self.S

    @property
    def storage(self) -> str:
        """Factor storage layout actually held ("dense" | "packed")."""
        return "packed" if isinstance(self.L, PackedBlocks) else "dense"

    def outputs(self) -> dict:
        """Stage outputs keyed by stage name (the stage-graph view)."""
        out = {"dual": self.F}
        if self.Sb is not None:
            out["dirichlet"] = self.Sb
        return out

    def device_bytes(self) -> dict:
        """Device bytes of the persistent solution-phase stacks.

        ``K`` is always packed; ``L`` is packed or dense per
        ``cfg.storage``; ``dense_L``/``dense_K`` report what the dense
        (S, n, n) stacks would cost — the packed-vs-dense headline number.
        ``per_stage`` attributes the persistent bytes to their stage graph
        node (the factor + lumped K + B̃ᵀ live with the dual stage).
        """
        def nbytes(x):
            if x is None:
                return 0
            if isinstance(x, PackedBlocks):
                return x.nbytes
            return int(np.prod(x.shape)) * x.dtype.itemsize

        n = self.index.n
        dense_one = self.S * n * n * jnp.result_type(self.Btp).itemsize
        out = {
            "L": nbytes(self.L),
            "K": nbytes(self.K),
            "Btp": nbytes(self.Btp),
            "F": nbytes(self.F),
            "Sb": nbytes(self.Sb),
            "Btb": nbytes(self.Btb),
            "Kreg": nbytes(self.Kreg),
            "dense_L": dense_one,
            "dense_K": dense_one,
        }
        out["total"] = (out["L"] + out["K"] + out["Btp"] + out["F"]
                        + out["Sb"] + out["Btb"] + out["Kreg"])
        per_stage = {"dual": out["L"] + out["K"] + out["Btp"] + out["F"]
                     + out["Kreg"]}
        if self.Sb is not None:
            per_stage["dirichlet"] = out["Sb"] + out["Btb"]
        out["per_stage"] = per_stage
        return out


def batched_assemble(
    L: Union[jax.Array, PackedBlocks],
    Btp: jax.Array,
    col_perm: Optional[jax.Array],
    inv_col_perm: Optional[jax.Array],
    env: SteppedMeta,
    cfg: SchurAssemblyConfig,
    block_mask: Optional[np.ndarray],
) -> jax.Array:
    """Assemble all subdomain SCs in one vmapped program.

    Per-subdomain *column* permutations (each subdomain has its own stepped
    order) are applied as batched gathers around a single envelope-metadata
    assembler. Pass ``col_perm=None`` when B̃ᵀ is already stepped — the
    §Perf path: relabel local multipliers host-side once (the column order
    is arbitrary), and the runtime permute gathers (which GSPMD can only
    partition by replicating) vanish entirely. The paper pays for these
    permutes on every assembly (§4.4); relabeling removes them for free.
    """
    assembler = make_assembler(env, cfg, block_mask)

    if col_perm is None:
        return jax.vmap(assembler)(L, Btp)

    def one(Ls, Bs, cp, icp):
        Bpp = jnp.take(Bs, cp, axis=1)  # stepped column order
        Fp = assembler(Ls, Bpp)  # env has identity perm
        return jnp.take(jnp.take(Fp, icp, axis=0), icp, axis=1)

    return jax.vmap(one)(L, Btp, col_perm, inv_col_perm)


def bt_pattern(sd) -> np.ndarray:
    """(n, m_max) nonzero pattern of a subdomain's B̃ᵀ, from its compact
    (b_rows, m) gluing record — also for pattern-only decompositions
    (``decompose_problem(..., assemble_values=False)``), whose dense Bt is
    a placeholder."""
    P = np.zeros((sd.n, len(sd.b_rows)), dtype=bool)
    P[sd.b_rows[: sd.m], np.arange(sd.m)] = True
    return P


def _share_valid(problem: FetiProblem,
                 split: dirlib.BoundaryInteriorSplit) -> bool:
    """The interior-factor dedup is valid iff every subdomain's fixing
    DOFs lie on the (union) boundary: the fixing-DOF regularization then
    only shifts boundary diagonal entries, so the dual factor's leading
    (n_i, n_i) principal block is the Cholesky factor of the UNREGULARIZED
    K_ii — exactly what the Dirichlet stage eliminates against."""
    bset = np.zeros(split.n, dtype=bool)
    bset[split.boundary] = True
    return all(bool(bset[sd.fixing_dofs].all())
               for sd in problem.subdomains)


def subdomain_chunk(fc: FetiConfig, S: int) -> int:
    """Subdomains one step of the compiled prep processes together: all of
    them, except for f64 stacks on a TPU. XLA:TPU has no f64 units; it
    emulates f64 products with temporaries that grow with the batch (the
    16-subdomain f64 feti-elasticity-2d prep with the Dirichlet stage asks
    for 25 GB of a v5e's 16 GB), so there the prep walks the subdomains one
    at a time."""
    if fc.dtype_name == "f64" and jax.default_backend() == "tpu":
        return 1
    return S


def _map_chunks(prep: Callable, chunk: int) -> Callable:
    """Run a subdomain-batched ``prep`` over ``chunk``-sized slices of its
    stacks in sequence (``lax.map``); outputs are re-stacked."""

    def run(*stacks):
        S = jax.tree_util.tree_leaves(stacks)[0].shape[0]
        if S % chunk:
            raise ValueError(f"{S} subdomains do not split into chunks of "
                             f"{chunk}")

        def split(x):
            return x.reshape((S // chunk, chunk) + x.shape[1:])

        def join(x):
            return x.reshape((S,) + x.shape[2:])

        out = jax.lax.map(lambda xs: prep(*xs),
                          jax.tree_util.tree_map(split, stacks))
        return jax.tree_util.tree_map(join, out)

    return run


def make_cluster_preprocessor(problem: FetiProblem, config=None,
                              **deprecated):
    """Build the COMPILED preprocessing function for one decomposition.

    ``config`` is a :class:`~repro.feti.config.FetiConfig` (or its
    coercion sugar: a bare ``SchurAssemblyConfig``, ``"auto"``, ``None``).
    Pre-FetiConfig keyword arguments still work via ``**deprecated`` but
    emit a ``DeprecationWarning``.

    Returns (static, prep) where ``prep`` is jitted once per sparsity
    pattern in the process — the paper's symbolic/numeric split. The
    symbolic phase runs for every call; the jitted program is kept
    (:mod:`repro.feti.programs`) by the digest of everything it bakes in
    (the column permutations, the stepped envelope and fill masks, the
    packed index, the resolved configs, the split, the dtypes, the mesh),
    so a later decomposition of the same structure, whatever its values,
    gets the program an earlier one built and traces nothing. Multi-step
    simulations recall ``prep`` with new values at zero recompiles.
    ``static`` carries the host-side symbolic products, including the
    resolved per-stage configs, (if autotuned) the joint
    :class:`GraphPlan`, ``programs`` (the structure's
    :class:`~repro.feti.programs.StructurePrograms`) and ``prep_program``:
    ``"hit"`` when the program was an earlier one's, else ``"miss"``.

    Every assembly stage is declared as a :class:`StageSpec` and the set
    is planned as ONE :class:`StageGraph` when ``schur == "auto"`` — a
    single joint cache entry covers the dual operator AND the Dirichlet
    boundary stage. When the factor-sharing conditions hold
    (:func:`_share_valid`; ``share_factor`` in FetiConfig) the dual rows
    are ordered ``split.dperm`` and the Dirichlet stage reuses the dual
    factor's leading principal block instead of factorizing K_ii.

    With ``mesh`` set, ``prep`` expects subdomain-sharded stacks whose
    multiplier columns are already relabeled into each subdomain's stepped
    order (:func:`repro.feti.sharded.relabel_columns`) and runs
    factorization + the ``col_perm=None`` assembler under ``shard_map`` —
    every device processes exactly its slice of subdomains, no exchange.
    """
    fc = _coerce_config(config, deprecated, "make_cluster_preprocessor")
    explicit, dirichlet = fc.explicit, fc.dirichlet
    ordering, storage, mesh = fc.ordering, fc.storage, fc.mesh
    cfg = fc.schur if fc.schur is not None else SchurAssemblyConfig()

    subs = problem.subdomains
    S = len(subs)
    n = subs[0].n
    ndpn = problem.ndof_per_node
    n_nodes = n // ndpn
    m_max = problem.m_max
    node_shape = tuple(e + 1 for e in problem.elems_per_sub)

    # ---- symbolic phase (host, shared by all subdomains) ----
    nperm = node_ordering(node_shape, ordering)
    lmesh = structured_mesh(problem.elems_per_sub)
    npat0 = matrix_pattern_from_elems(n_nodes, lmesh.elems)
    kpat0 = expand_node_pattern(npat0, ndpn)  # original DOF order
    # vector problems: node-blocked DOFs stay adjacent under the expanded
    # permutation, and the DOF pattern is the node pattern with every
    # entry blown up to an (ndpn, ndpn) block — the natural stress case
    # for the block-sparse packed factor layout
    fill_perm = expand_node_perm(nperm, ndpn)

    # ---- Dirichlet stage symbolic phase + factor-sharing decision ----
    # the ONE boundary/interior split: computed here, threaded into every
    # dirlib consumer (dof_perm/kpat passed down so nothing is rebuilt)
    split = None
    share = False
    if dirichlet:
        split = dirlib.boundary_interior_split(problem, ordering=ordering,
                                               dof_perm=fill_perm)
        if fc.share_factor is not False and split.n_i > 0:
            ok = _share_valid(problem, split)
            if fc.share_factor is True and not ok:
                raise ValueError(
                    "share_factor=True, but some subdomain's fixing DOFs "
                    "are interior — the regularization would perturb the "
                    "shared interior factor. Use share_factor='auto'.")
            share = ok

    # factor row order: the boundary/interior layout when sharing (the
    # interior keeps its fill-reducing elimination order, so the leading
    # principal block of L is the interior factor), the plain
    # fill-reducing order otherwise
    node_perm = split.dperm if share else fill_perm
    kpat = kpat0[node_perm][:, node_perm]
    patterns = [bt_pattern(sd)[node_perm] for sd in subs]

    # builders used both by the joint planner (scoring candidate block
    # sizes) and below to materialize the symbolic products for the final
    # configs; memoized so the winning size isn't analyzed twice
    _built: dict = {}

    def _symbolic(bs: int, rbs: int):
        key = (bs, rbs)
        if key not in _built:
            # regularization only touches the diagonal: pattern unchanged
            mask = block_symbolic_cholesky(block_pattern(kpat, bs))
            metas = [
                build_stepped_meta(p, block_size=bs, rhs_block_size=rbs)
                for p in patterns
            ]
            _built[key] = (metas, shared_envelope(metas), mask)
        return _built[key]

    _dbuilt: dict = {}

    def _dsymbolic(bs: int, rbs: int):
        key = (bs, rbs)
        if key not in _dbuilt:
            _dbuilt[key] = dirlib.dirichlet_symbolic(
                problem, split, bs, rbs, kpat=kpat0)
        return _dbuilt[key]

    # ---- the stage graph: every assembly stage, planned as one unit ----
    from repro.core import column_pivots

    piv = np.stack([column_pivots(p) for p in patterns])
    stage_dtype = fc.dtype_name
    specs = [StageSpec(
        name="dual",
        builder=lambda bs, rbs: _symbolic(bs, rbs)[1:],
        fingerprint=pattern_fingerprint(
            piv, n, m_max,
            extra=[kpat.sum(axis=1).astype(np.int64), node_perm]),
        n=n, storage=storage, dtype=stage_dtype,
        # without explicit assembly only the factorization block size
        # matters — don't burn timed assembly micro-runs on it
        measure=None if explicit else "never",
    )]
    if dirichlet and split.n_i > 0:
        specs.append(StageSpec(
            name="dirichlet",
            builder=_dsymbolic,
            fingerprint=dirlib.dirichlet_fingerprint(problem, split,
                                                     kpat=kpat0),
            n=split.n_i, storage=storage, dtype=stage_dtype,
            share_factor_of="dual" if share else None,
        ))
    graph = StageGraph(specs)

    plan = d_plan = gplan = None
    if fc.auto:
        gplan = graph.plan(measure=fc.measure, cache=fc.plan_cache)
        plan = gplan["dual"]
        cfg = plan.cfg
        d_plan = gplan.plans.get("dirichlet")
    elif storage is not None and storage != cfg.storage:
        cfg = dataclasses.replace(cfg, storage=storage)
    d_cfg = None
    if dirichlet:
        d_cfg = d_plan.cfg if d_plan is not None else cfg

    cfgs = {"dual": cfg}
    if "dirichlet" in graph.by_name:
        cfgs["dirichlet"] = d_cfg
    resolved = graph.resolve(cfgs, plans=gplan.plans if gplan else None)

    env, block_mask = resolved["dual"].meta, resolved["dual"].mask
    metas = _built[(cfg.block_size, cfg.rhs_bs)][0]
    index = PackedBlockIndex.from_mask(block_mask, n, cfg.block_size)
    meta_ib = mask_ii = d_assemble = None
    if dirichlet:
        if "dirichlet" in resolved:
            meta_ib = resolved["dirichlet"].meta
            mask_ii = resolved["dirichlet"].mask
        d_assemble = dirlib.make_dirichlet_assembler(
            split, meta_ib, mask_ii, d_cfg, shared=share)
    col_perms = np.empty((S, m_max), dtype=np.int64)
    inv_col_perms = np.empty((S, m_max), dtype=np.int64)
    for i, me in enumerate(metas):
        col_perms[i] = me.perm
        inv_col_perms[i] = me.inv_perm

    cp = jnp.asarray(col_perms)
    icp = jnp.asarray(inv_col_perms)
    packed = cfg.storage == "packed"
    chunk = subdomain_chunk(fc, S) if mesh is None else S

    def _factorize(Kp_l):
        """Batched numerical factorization in the configured storage.
        ``Kp_l`` is the packed (S, n_blocks, bs, bs) regularized K stack;
        dense storage unpacks it transiently inside the program."""
        with jax.named_scope("factorize"):
            if packed:
                return jax.vmap(lambda v: block_cholesky_packed(
                    PackedBlocks(v, index), index))(Kp_l)
            return jax.vmap(lambda v: block_cholesky(
                index.unpack_symmetric(v), cfg.block_size, mask=block_mask)
            )(Kp_l)

    ni = split.n_i if split is not None else 0

    def _interior_factor(L):
        """Leading (n_i, n_i) principal block of the dual factor stack —
        the shared interior factor. A packed factor densifies transiently
        inside the compiled program (the slice itself never persists)."""
        Ld = L.unpack() if isinstance(L, PackedBlocks) else L
        return Ld[:, :ni, :ni]

    def _dirichlet_stage(L, Kp_l, *dir_l):
        """The boundary-Schur node of the graph, shared by the local and
        shard_map preps. ``dir_l`` is (Kbb, Zb) when the interior factor
        is shared — K_ib is the dual factor input's off-diagonal slice,
        unperturbed by the boundary-diagonal regularization (read as the
        transpose of the stored lower slice K_bi) — and (Kd, Zb)
        otherwise."""
        with jax.named_scope("stage:dirichlet"):
            if share:
                Kbb_l, Zb_l = dir_l
                Kib = jnp.swapaxes(index.unpack(Kp_l)[:, ni:, :ni], -1, -2)
                Sb = jax.vmap(d_assemble)(_interior_factor(L), Kib, Kbb_l)
            else:
                Kd_l, Zb_l = dir_l
                Sb = jax.vmap(d_assemble)(Kd_l)
            return jax.vmap(dirlib.restrict_own_boundary)(Sb, Zb_l)

    if mesh is None:

        def prep_cols(Kp_stack, Btp_stack, cp_l, icp_l, *dir_stacks):
            L = _factorize(Kp_stack)
            F = None
            if explicit:
                with jax.named_scope("stage:dual"):
                    F = batched_assemble(L, Btp_stack, cp_l, icp_l, env,
                                         cfg, block_mask)
            if dirichlet:
                return L, F, _dirichlet_stage(L, Kp_stack, *dir_stacks)
            return L, F

        run = prep_cols if chunk >= S else _map_chunks(prep_cols, chunk)

        def prep(Kp_stack, Btp_stack, *dir_stacks):
            return run(Kp_stack, Btp_stack, cp, icp, *dir_stacks)

    else:
        from jax.sharding import PartitionSpec as P

        def _local(Kp_l, Btp_l, *dir_l):
            outs = [_factorize(Kp_l)]
            if explicit:
                # columns were relabeled host-side: col_perm=None fast path
                with jax.named_scope("stage:dual"):
                    outs.append(batched_assemble(outs[0], Btp_l, None, None,
                                                 env, cfg, block_mask))
            if dirichlet:
                outs.append(_dirichlet_stage(outs[0], Kp_l, *dir_l))
            return tuple(outs)

        n_in = 4 if dirichlet else 2
        n_out = 1 + int(explicit) + int(dirichlet)

        def prep(Kp_stack, Btp_stack, *dir_stacks):
            outs = shlib.shard_map(
                _local, mesh=mesh,
                in_specs=(P(shlib.AXIS),) * n_in,
                out_specs=(P(shlib.AXIS),) * n_out,
            )(Kp_stack, Btp_stack, *dir_stacks)
            it = iter(outs)
            L = next(it)
            F = next(it) if explicit else None
            if dirichlet:
                return L, F, next(it)
            return L, F

    # bf16 is storage-only: no backend has a bf16 Cholesky/TRSM, and the
    # Pallas kernels already accumulate sub-f32 inputs in f32 — so the
    # compiled prep upcasts its inputs to the compute dtype (f32), runs
    # the whole graph there, and rounds the persistent outputs back to
    # the storage dtype. For f64/f32 the wrapper is not installed and the
    # program is byte-identical to the unwrapped one.
    sdt = fc.dtype_np
    cdt = np.dtype(precision.compute_dtype(fc.dtype))
    if cdt != sdt:
        inner_prep = prep

        def _cast_tree(tree, dt):
            return jax.tree_util.tree_map(
                lambda x: x.astype(dt)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)

        def prep(*stacks):
            return _cast_tree(inner_prep(*_cast_tree(stacks, cdt)), sdt)

    # everything prep bakes in; node_perm and the planner's cost reports
    # only feed the host stacks and the reports
    key = proglib.digest(col_perms, inv_col_perms, env, block_mask, index,
                         cfg, d_cfg, split, share, meta_ib, mask_ii,
                         explicit, dirichlet, mesh, chunk, sdt, cdt,
                         np.dtype(fc.solve_dtype))
    programs, hit = proglib.for_structure(key, prep)
    static = dict(node_perm=node_perm, block_mask=block_mask, env=env,
                  col_perm=cp, inv_col_perm=icp, cfg=cfg, plan=plan,
                  index=index, split=split, dirichlet_cfg=d_cfg,
                  dirichlet_plan=d_plan, dirichlet_env=meta_ib,
                  dirichlet_mask=mask_ii, graph=graph, graph_plan=gplan,
                  stages=resolved, share=share, programs=programs,
                  prep_program="hit" if hit else "miss")
    return static, programs.prep


def _pad_packed_identity(vals: np.ndarray, S_pad: int,
                         index: PackedBlockIndex) -> np.ndarray:
    """Pad a packed (S, n_blocks, bs, bs) stack with identity matrices (the
    inert dummy subdomains of a mesh-padded cluster)."""
    pad = np.zeros((S_pad - vals.shape[0],) + vals.shape[1:], vals.dtype)
    pad[:, index.diag_slots] = np.eye(index.bs, dtype=vals.dtype)
    return np.concatenate([vals, pad], axis=0)


def host_stacks(problem: FetiProblem, static: dict, config=None) -> dict:
    """The numeric inputs of the compiled ``prep``, plus the packed K stacks
    the solution phase keeps, built on the host ONE subdomain at a time.

    Every matrix is regularized, permuted and packed per subdomain in
    numpy, so no dense (S, n, n) stack exists beyond the problem's own
    list of subdomain matrices, and nothing dense reaches a device.
    ``static`` is the first value :func:`make_cluster_preprocessor`
    returns. Keys, all numpy, at the storage dtype unless noted:

      * ``Kp``: (S, n_blocks, bs, bs) packed regularized K in factor row
        order, the diagonal tail identity-padded (factorizable);
      * ``K``: the same layout of the unregularized K (lumped
        preconditioner);
      * ``Kreg``: ``Kp`` at f64 with a zero tail, the matrix refinement
        closes its loop against — only when refinement is on, else None;
      * ``Btp``: (S, n, m_max) row-permuted B̃ᵀ;
      * with the Dirichlet stage: ``Kd`` (the (S, n_b, n_b) unregularized
        K_bb when the interior factor is shared, else the dperm-ordered
        K), ``Btb`` and ``Zb``.
    """
    fc = as_feti_config(config)
    dtype = fc.dtype_np
    subs = problem.subdomains
    S = len(subs)
    node_perm = static["node_perm"]
    index: PackedBlockIndex = static["index"]
    split, share = static["split"], static["share"]
    shape = (S, index.n_blocks, index.bs, index.bs)
    Kp = np.empty(shape, dtype)
    K = np.empty(shape, dtype)
    Kreg = np.empty(shape, np.float64) if fc.resolved_refine() > 0 else None
    Kd = []
    inv_perm = np.argsort(node_perm)
    for i, sd in enumerate(subs):
        Ki = index.pack_host(sd.K, np.float64, perm=node_perm)
        K[i] = Ki
        # fixing_dofs_regularization's shift ρ = mean(diag K) on the fixing
        # DOFs' diagonal, at their packed positions (factor row order)
        q = inv_perm[sd.fixing_dofs]
        slot = index.diag_slots[q // index.bs]
        Ki[slot, q % index.bs, q % index.bs] += float(np.mean(np.diag(sd.K)))
        Kp[i] = Ki
        if Kreg is not None:
            Kreg[i] = Ki
        if fc.dirichlet:
            # the dirichlet stage eliminates against the UNREGULARIZED K:
            # K_ii is SPD outright (boundary nonempty pins the kernel) and
            # the fixing-DOF diagonal shift would perturb S_b on boundary
            # entries. Shared interior factor: only K_bb is streamed — K_ii
            # and K_ib already enter through the dual stage's K, whose
            # interior rows the regularization cannot touch.
            rows = split.boundary if share else split.dperm
            Kd.append(sd.K[rows][:, rows])
    # identity on the padded diagonal tail keeps Kp factorizable
    tail = np.arange(index.n - (index.nb - 1) * index.bs, index.bs)
    Kp[:, index.diag_slots[-1], tail, tail] = 1.0
    out = dict(Kp=Kp, K=K, Kreg=Kreg,
               Btp=np.stack([sd.Bt[node_perm] for sd in subs]))
    if fc.dirichlet:
        out.update(Kd=np.stack(Kd),
                   Btb=np.stack([sd.Bt[split.boundary] for sd in subs]),
                   Zb=dirlib.own_boundary_masks(problem, split))
    return out


def preprocess_cluster(problem: FetiProblem, config=None,
                       **deprecated) -> ClusterState:
    """Paper §2.2 'preprocessing': factorize every K_i and (if explicit)
    assemble every F̃ᵢ with the sparsity-utilizing pipeline.

    ``config`` is a :class:`~repro.feti.config.FetiConfig`, or one of its
    shorthand forms: a bare ``SchurAssemblyConfig``, the string ``"auto"``
    (the stage graph plans every assembly stage jointly — the chosen plans
    are available as ``ClusterState.graph_plan`` and the resolved per-stage
    configs as ``ClusterState.stages``), or ``None`` for defaults.
    Pre-FetiConfig keyword arguments (``cfg=``, ``explicit=``,
    ``dirichlet=``, ...) still work but emit a ``DeprecationWarning``.

    ``FetiConfig.storage`` overrides the factor storage layout: "packed"
    keeps every Cholesky factor as a
    :class:`~repro.sparse.packed.PackedBlocks` stack in the symbolic
    fill-mask layout (O(S·nnz_blocks) device memory), "dense" keeps
    (S, n, n) stacks. ``None`` defers to the assembly config (or lets the
    planner choose). The unregularized K kept for the lumped
    preconditioner is ALWAYS packed — no dense (S, n, n) K survives
    preprocessing in either mode.

    ``preconditioner="dirichlet"`` additionally assembles (inside the same
    compiled program) the per-subdomain primal boundary Schur complements
    S_b = K_bb − K_bi K_ii⁻¹ K_ib (:mod:`repro.feti.dirichlet`); the state
    then carries ``Sb``, the boundary-row B̃ᵀ slice ``Btb``, the split and
    the stage's own resolved config/plan. When the factor-sharing
    conditions hold (``ClusterState.shared_factor``) the stage reuses the
    dual factor's interior principal block and the preprocessor streams
    only the (S, n_b, n_b) unregularized K_bb instead of a full (S, n, n)
    copy of K.

    Pass ``FetiConfig(mesh=...)`` (``("data",)`` axis,
    :func:`repro.launch.mesh.make_feti_mesh`) to shard the subdomain axis
    over devices: multipliers are relabeled to stepped column order
    host-side, the cluster is padded to a multiple of the mesh size with
    inert identity subdomains, and all stacks land sharded. ``mesh=None``
    is bit-for-bit the single-device behavior.
    """
    fc = _coerce_config(config, deprecated, "preprocess_cluster")
    dirichlet, mesh, dtype = fc.dirichlet, fc.mesh, fc.dtype_np
    subs = problem.subdomains
    S = len(subs)
    tr = current_tracer()
    # initialization (paper's symbolic phase): host symbolic products +
    # joint stage-graph planning (the planner opens plan:* child spans)
    with tr.span("init") as sp:
        static, prep = make_cluster_preprocessor(problem, fc)
        sp.set(prep_program=static["prep_program"])
    cfg = static["cfg"]  # resolved when "auto"/storage override was passed
    node_perm = static["node_perm"]
    index: PackedBlockIndex = static["index"]
    split = static["split"]
    share = static["share"]

    with tr.span("host_stacks"):
        stacks = host_stacks(problem, static, fc)
    Kp, Btp, K_vals = stacks["Kp"], stacks["Btp"], stacks["K"]
    Kreg_vals = stacks["Kreg"]
    Kd, Btb, Zb = stacks.get("Kd"), stacks.get("Btb"), stacks.get("Zb")
    f = np.stack([sd.f for sd in subs])
    lam = np.stack([sd.lambda_ids for sd in subs])

    if mesh is None:
        S_pad = S

        def to_dev(x, dt=dtype):
            return jnp.asarray(x, dtype=dt)

    else:
        # relabel multiplier columns into each subdomain's stepped order
        # (arbitrary by construction) so the assembler and dual operator
        # run permute-free, then pad to a mesh-size multiple with inert
        # identity subdomains glued to nothing (ids -> the dummy slot)
        cp_np = np.asarray(static["col_perm"])
        Btp = shlib.relabel_columns(Btp, cp_np)
        lam = shlib.relabel_columns(lam, cp_np)
        S_pad = shlib.padded_count(S, mesh)
        Kp = _pad_packed_identity(Kp, S_pad, index)
        if Kreg_vals is not None:
            Kreg_vals = _pad_packed_identity(Kreg_vals, S_pad, index)
        Btp = shlib.pad_stack(Btp, S_pad)
        K_vals = shlib.pad_stack(K_vals, S_pad)
        f = shlib.pad_stack(f, S_pad)
        if dirichlet:
            # dummy subdomains: identity K (factorizable interior, S_b = I)
            # glued to nothing (zero Btb, zero own-boundary mask), so they
            # contribute nothing; in shared mode the streamed K_bb slice
            # is identity for the same reason
            Kd = shlib.pad_stack(Kd, S_pad, identity=True)
            Btb = shlib.pad_stack(shlib.relabel_columns(Btb, cp_np), S_pad)
            Zb = shlib.pad_stack(Zb, S_pad)
        pad_ids = np.full((S_pad - S, lam.shape[1]), problem.n_lambda,
                          lam.dtype)
        lam = np.concatenate([lam, pad_ids], axis=0)

        def to_dev(x, dt=dtype):
            return shlib.shard_stack(mesh, np.asarray(x, dtype=dt))

    R_stack = np.stack([sd.R for sd in subs])  # (S, n, k) original order
    if mesh is not None:
        R_stack = shlib.pad_stack(R_stack, S_pad)  # zero kernels for dummies

    Kp_j = to_dev(Kp)
    Btp_j = to_dev(Btp)
    Sb = Btb_j = None
    # the compiled numeric phase: one prep call computes every stage; the
    # child spans attribute wall time by syncing each stage's outputs in
    # graph order (the dirichlet span measures the tail the boundary
    # stage adds beyond the dual factor + F̃)
    with tr.span("prep"):
        if dirichlet:
            Btb_j = to_dev(Btb)
            with tr.span("stage:dual") as sp:
                L, F, Sb = prep(Kp_j, Btp_j, to_dev(Kd), to_dev(Zb))
                sp.sync(L, F)
            with tr.span("stage:dirichlet") as sp:
                sp.sync(Sb)
        else:
            with tr.span("stage:dual") as sp:
                L, F = prep(Kp_j, Btp_j)
                sp.sync(L, F)

    # the packed K stacks were built host-side (host_stacks): place/shard
    # only their values
    with tr.span("pack") as sp_pack:
        K_packed = PackedBlocks(to_dev(K_vals), index)
        refine_steps = fc.resolved_refine()
        Kreg_packed = None
        if Kreg_vals is not None:
            Kreg_packed = PackedBlocks(to_dev(Kreg_vals, dt=np.float64),
                                       index)

        # loads / kernel bases carry the SOLVE dtype (f64 whenever
        # refinement recovers f64 accuracy): the coarse problem, dual RHS
        # and PCPG outer vectors are built from them. For f64 configs this
        # IS fc.dtype.
        sdt = fc.solve_dtype
        f_j = to_dev(f, dt=sdt)
        fp_j = to_dev(f[:, node_perm], dt=sdt)
        sp_pack.sync(K_packed.values, f_j, fp_j)
    return ClusterState(
        problem=problem,
        cfg=cfg,
        plan=static["plan"],
        env=static["env"],
        block_mask=static["block_mask"],
        node_perm=node_perm,
        index=index,
        L=L,
        Btp=Btp_j,
        K=K_packed,
        F=F,
        f=f_j,
        fp=fp_j,
        lambda_ids=to_dev(lam, dt=None),
        col_perm=static["col_perm"],
        inv_col_perm=static["inv_col_perm"],
        R=to_dev(R_stack, dt=sdt),
        Kreg=Kreg_packed,
        refine_steps=refine_steps,
        mesh=mesh,
        n_real=S if mesh is not None else None,
        relabeled=mesh is not None,
        prep=prep,
        programs=static["programs"],
        split=split,
        Sb=Sb,
        Btb=Btb_j,
        dirichlet_cfg=static["dirichlet_cfg"],
        dirichlet_plan=static["dirichlet_plan"],
        dirichlet_env=static["dirichlet_env"],
        dirichlet_mask=static["dirichlet_mask"],
        stages=static["stages"],
        graph_plan=static["graph_plan"],
        shared_factor=share,
    )
