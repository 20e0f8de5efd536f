"""SparseFFN: pruned-weight FFN served through the paper's hybrid policy.

The TPU re-targeting of H-SPA(t)/H-HASH(t) (DESIGN.md §3.1): the switching
statistic is block-level density instead of per-column Op_j, and the
execution regimes are
  * dense path  — plain MXU matmul (the SPA analogue: dense accumulator,
    throughput-optimal when most blocks are present), chosen when the kept-
    block fraction >= ``t_density``;
  * sparse path — the BSR Pallas kernel (kernels/bsr_spmm.py), which skips
    absent blocks entirely (the SPARS/HASH analogue), chosen for sparser
    weights;
  * spgemm path — the *differentiable* re-targeting (DESIGN.md §10): the
    pruned weight is stored as an element-level CSC whose values are
    trainable, activations ride as dense-pattern CSC value arrays, and the
    multiply is the cached SpGEMM plan's device stream
    (``core.jax_stream``) — jit-compatible and reverse-differentiable, so a
    sparse FFN can *train* with SpGEMM inside the traced step
    (``training.train_loop.build_sparse_ffn_train_step``).  Opt-in via
    ``path="spgemm"``; weight patterns are static across steps (pruned at
    conversion time), so each distinct token count plans once and every
    later step is a pure compiled replay.

``from_dense`` prunes by block magnitude to a target density. The policy is
per-matrix, decided at conversion time (weights are static at serving time,
exactly like the paper's pre-processing phase).
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bsr_spmm import bsr_from_dense, bsr_spmm
from repro.sparse.format import CSC, csc_from_dense


@dataclasses.dataclass
class SparseMatmul:
    """One pruned weight matrix with its chosen execution path."""

    path: str                   # "dense" | "bsr" | "spgemm"
    dense_w: jax.Array | None
    block_idx: jax.Array | None
    block_nnz: jax.Array | None
    blocks: jax.Array | None
    shape: tuple
    density: float
    w_csc: CSC | None = None    # spgemm path: static pattern, jnp values
    #: spgemm path: per-plan plan-memory-guard override (products); large
    #: FFNs * long token blocks exceed the global default, and mutating
    #: fast.STREAM_MAX_PRODUCTS would re-key every cached plan
    stream_limit: int | None = None
    # spgemm path: per-token-count SpGEMM plan + densify indices, resolved
    # once at trace time.  A bounded LRU: each entry pins a plan (host +
    # device stream, O(nnz_w * N)) past plan-LRU eviction, so workloads
    # cycling through many distinct token counts must not accumulate them
    _spgemm_memo: "OrderedDict" = dataclasses.field(
        default_factory=OrderedDict, repr=False)

    SPGEMM_MEMO_SIZE = 8        # distinct token counts held per matrix

    @classmethod
    def from_dense(cls, w, *, bm=8, bk=8, keep_density=0.5,
                   t_density=0.75, path: str | None = None,
                   stream_limit: int | None = None) -> "SparseMatmul":
        """Prune ``w`` by block magnitude and pick an execution path.

        ``path=None`` applies the serving policy (dense above ``t_density``,
        BSR below); ``path="spgemm"`` forces the differentiable CSC/SpGEMM
        path (DESIGN.md §10), whose values are trainable; ``"dense"`` /
        ``"bsr"`` force the serving paths.  ``stream_limit`` raises this
        matrix's plan-memory guard (the spgemm path's stream holds
        ``nnz_w * tokens`` products, which outgrows the global default at
        large FFN sizes) without touching the global knob.
        """
        if path not in (None, "dense", "bsr", "spgemm"):
            raise ValueError(
                f"unknown path {path!r}; None, 'dense', 'bsr' or 'spgemm'")
        w = np.asarray(w, np.float32)
        m, k = w.shape
        n_rb, n_cb = m // bm, k // bk
        tiles = w.reshape(n_rb, bm, n_cb, bk).transpose(0, 2, 1, 3)
        norms = np.abs(tiles).max(axis=(2, 3))
        n_keep = max(1, int(round(keep_density * n_rb * n_cb)))
        thresh = np.partition(norms.reshape(-1), -n_keep)[-n_keep]
        pruned = np.where((norms >= thresh)[:, :, None, None], tiles, 0.0)
        w_pruned = pruned.transpose(0, 2, 1, 3).reshape(m, k)
        density = float((norms >= thresh).mean())
        if path == "spgemm":
            csc = csc_from_dense(w_pruned)
            csc = CSC(jnp.asarray(np.asarray(csc.values, np.float32)),
                      csc.row_indices, csc.col_ptr, csc.shape)
            return cls("spgemm", None, None, None, None, (m, k), density,
                       w_csc=csc, stream_limit=stream_limit)
        if path == "dense" or (path is None and density >= t_density):
            # paper's hybrid switch: stay dense (SPA)
            return cls("dense", jnp.asarray(w_pruned), None, None, None,
                       (m, k), density)
        bi, bn, blocks = bsr_from_dense(w_pruned, bm, bk)
        return cls("bsr", None, jnp.asarray(bi), jnp.asarray(bn),
                   jnp.asarray(blocks), (m, k), density)

    @classmethod
    def from_shared_pattern(cls, w_stack, *, keep_density=0.5,
                            stream_limit: int | None = None):
        """Shared-pattern spgemm matmuls for a stack of same-shape weights.

        The serving-engine regime (DESIGN.md §12): scanned super-blocks
        need every repeated layer to share *one* CSC structure so the scan
        body traces once and all reps replay the same cached plan — the
        paper's static pre-processing contract, batched over depth.
        ``w_stack`` is ``[R, m, k]`` in ``W @ x`` orientation; pruning
        keeps the element positions whose rep-wise max magnitude lands in
        the top ``keep_density`` fraction (element granularity: one mask
        must serve every rep, so block-local magnitudes of a single layer
        cannot decide it).  Returns ``(matmul, values)`` where ``matmul``
        holds rep 0's values and ``values`` is the ``[R, nnz]`` trainable
        stack in the pattern's CSC (column-major) order.
        """
        w = np.asarray(w_stack, np.float32)
        if w.ndim != 3:
            raise ValueError(f"w_stack must be [R, m, k], got {w.shape}")
        _, m, k = w.shape
        mag = np.abs(w).max(axis=0)
        n_keep = max(1, int(round(keep_density * m * k)))
        thresh = np.partition(mag.reshape(-1), -n_keep)[-n_keep]
        cols, rows = np.nonzero((mag >= thresh).T)   # CSC coordinate order
        col_ptr = np.zeros(k + 1, np.int64)
        np.cumsum(np.bincount(cols, minlength=k), out=col_ptr[1:])
        values = w[:, rows, cols]                    # [R, nnz], CSC order
        csc = CSC(jnp.asarray(values[0]), rows.astype(np.int32),
                  col_ptr.astype(np.int32), (m, k))
        mat = cls("spgemm", None, None, None, None, (m, k),
                  float(rows.size / (m * k)), w_csc=csc,
                  stream_limit=stream_limit)
        return mat, jnp.asarray(values)

    # -- spgemm path (DESIGN.md §10) -------------------------------------

    @property
    def w_values(self) -> jax.Array:
        """Trainable weight values (spgemm path): the CSC value array."""
        if self.path != "spgemm":
            raise ValueError(
                f"w_values is the spgemm path's parameter array "
                f"(this matmul runs path={self.path!r})")
        return self.w_csc.values

    def _spgemm_plan(self, n: int, backend: str = "jax"):
        """Plan W @ X for X dense [K, N], memoized per token count.

        The activation operand is a *fully dense* pattern — its structure
        depends only on (K, N), so the symbolic phase runs once per
        distinct N (at trace time) and the numeric phase is the plan's
        jitted device stream (``backend="jax"``) or the vectorized numpy
        stream (``backend="host"``, the serving fallback — DESIGN.md §12).
        Returns ``(plan, scatter_rows, scatter_cols)`` where the scatter
        indices densify the canonical CSC result into ``[M, N]``
        (plan-static numpy, free under jit).
        """
        memo_key = (n, backend)
        if memo_key in self._spgemm_memo:
            self._spgemm_memo.move_to_end(memo_key)
            return self._spgemm_memo[memo_key]
        from repro.core.api import cached_plan

        m, k = self.shape
        x_pat = CSC(np.zeros(k * n, np.float32),
                    np.tile(np.arange(k, dtype=np.int32), n),
                    np.arange(n + 1, dtype=np.int32) * k, (k, n))
        w_pat = CSC(np.zeros(self.w_csc.nnz, np.float32),
                    self.w_csc.row_indices, self.w_csc.col_ptr,
                    self.shape)
        plan = cached_plan(w_pat, x_pat, "expand", backend=backend,
                           stream_limit=self.stream_limit)
        s = plan.stream
        if s is None:
            raise ValueError(
                "spgemm-path weight stream exceeds the plan-memory guard; "
                "pass stream_limit= to from_dense/from_params (per-plan "
                "override) or shrink the token block")
        cols = np.repeat(np.arange(n, dtype=np.int32),
                         np.diff(s.c_col_ptr))
        self._spgemm_memo[memo_key] = (plan, s.c_rows, cols)
        while len(self._spgemm_memo) > self.SPGEMM_MEMO_SIZE:
            self._spgemm_memo.popitem(last=False)
        return self._spgemm_memo[memo_key]

    def apply_values(self, w_values, x):
        """y [M, N] = W @ x for trainable values ``w_values`` (spgemm path).

        Pure and jit/grad/vmap-compatible: ``w_values`` and ``x`` may be
        tracers; the plan lookup keys only on ``x``'s static shape.
        Column-major flattening turns the dense activations into the CSC
        value array of the plan's dense B pattern, and the plan's canonical
        result scatters back to dense through plan-static indices.
        """
        if self.path != "spgemm":
            raise ValueError(
                f"apply_values needs path='spgemm' (got {self.path!r})")
        n = x.shape[1]
        plan, rows, cols = self._spgemm_plan(int(n))
        c_vals = plan.stream_apply(w_values, x.T.reshape(-1))
        # plan-static, unique, in-bounds scatter indices: skip XLA's
        # bounds-check/dup handling (same rationale as the stream gathers)
        return jnp.zeros(self.shape[0:1] + (int(n),), c_vals.dtype).at[
            rows, cols].set(c_vals, mode="promise_in_bounds",
                            unique_indices=True)

    def apply_values_host(self, w_values, x) -> np.ndarray:
        """Host-stream spelling of :meth:`apply_values` (concrete numpy).

        The serving fallback path (DESIGN.md §12): while the device plan
        is still building/compiling in the background, a decode tick runs
        the same multiply through the *host* product stream — a cheap
        synchronous plan on the same LRU, no device lift and no XLA
        compile on the tick.  Concrete values only (never call under a
        trace); same contraction order as the host stream engine.
        """
        if self.path != "spgemm":
            raise ValueError(
                f"apply_values_host needs path='spgemm' (got {self.path!r})")
        x = np.asarray(x, np.float32)
        n = x.shape[1]
        plan, rows, cols = self._spgemm_plan(int(n), backend="host")
        c = plan.execute(np.asarray(w_values, np.float32),
                         x.T.reshape(-1), engine="stream")
        out = np.zeros((self.shape[0], int(n)), np.float32)
        out[rows, cols] = np.asarray(c.values, np.float32)
        return out

    def __call__(self, x, *, bn=None):
        """y = W @ x for x [K, N]."""
        if self.path == "dense":
            return self.dense_w @ x
        if self.path == "spgemm":
            return self.apply_values(self.w_values, x)
        n = x.shape[1]
        bn = bn or min(128, n)
        return bsr_spmm(self.block_idx, self.block_nnz, self.blocks, x,
                        bn=bn)

    def batched(self, xs, *, bn=None):
        """y [B, M, N] = W @ xs[b] for xs [B, K, N] — one launch for all B.

        The weight pattern is static (pruned at conversion time), so a batch
        of activations is exactly the same-pattern regime as batched SpGEMM
        (DESIGN.md §7): the BSR structure operands are shared and only the
        dense activations carry the batch axis, vmapped into a single
        leading-grid-dimension launch instead of B Python round-trips.
        """
        if self.path == "dense":
            # per-sample [M, K] @ [K, N] products, the loop's exact shapes: a
            # broadcast [M, K] @ [B, K, N] is one wider GEMM that sums in a
            # different order
            return jax.lax.map(lambda x: self.dense_w @ x, xs)
        if self.path == "spgemm":
            # same-pattern batched regime: the plan's vmapped device stream
            return jax.vmap(
                lambda x: self.apply_values(self.w_values, x))(xs)
        n = xs.shape[2]
        bn = bn or min(128, n)
        f = lambda x: bsr_spmm(self.block_idx, self.block_nnz, self.blocks,
                               x, bn=bn)
        return jax.vmap(f)(xs)

    @property
    def flops_per_col(self) -> int:
        m, k = self.shape
        if self.path == "dense":
            return 2 * m * k
        if self.path == "spgemm":
            return 2 * self.w_csc.nnz
        nb = int(np.asarray(self.block_nnz).sum())
        bm, bk = self.blocks.shape[2], self.blocks.shape[3]
        return 2 * nb * bm * bk


@dataclasses.dataclass
class SparseFFN:
    """SwiGLU FFN with pruned gate/up/down matrices."""

    gate: SparseMatmul
    up: SparseMatmul
    down: SparseMatmul

    @classmethod
    def from_params(cls, p, *, keep_density=0.4, t_density=0.75, bm=8, bk=8,
                    path: str | None = None,
                    stream_limit: int | None = None):
        mk = lambda w: SparseMatmul.from_dense(
            np.asarray(w).T, bm=bm, bk=bk, keep_density=keep_density,
            t_density=t_density, path=path, stream_limit=stream_limit)
        return cls(mk(p["gate"]["w"]), mk(p["up"]["w"]), mk(p["down"]["w"]))

    # -- differentiable spgemm path (DESIGN.md §10) ----------------------

    def trainable_params(self) -> dict:
        """The trainable weight-value pytree of an all-spgemm-path FFN."""
        mats = {"gate": self.gate, "up": self.up, "down": self.down}
        bad = [k for k, m in mats.items() if m.path != "spgemm"]
        if bad:
            raise ValueError(
                f"trainable_params needs every matmul on path='spgemm' "
                f"(convert with from_params(..., path='spgemm')); "
                f"{bad} are not")
        return {k: m.w_values for k, m in mats.items()}

    def apply(self, params, x):
        """Functional forward pass: ``params`` override the stored values.

        ``x`` is ``[T, D]`` (or a batch ``[B, T, D]``); the three matmuls
        run the differentiable SpGEMM stream with ``params['gate'/'up'/
        'down']`` as the weight values, so ``jax.grad`` of anything
        downstream reaches the sparse weights (the values of a *fixed*
        pruned pattern — structure never re-derives during training,
        exactly the paper's static pre-processing contract).
        """
        if x.ndim == 3:
            return jax.vmap(lambda xb: self.apply(params, xb))(x)
        xt = x.T                                   # [D, T]
        h = (jax.nn.silu(self.gate.apply_values(params["gate"], xt))
             * self.up.apply_values(params["up"], xt))
        return self.down.apply_values(params["down"], h).T

    def apply_host(self, params, x) -> np.ndarray:
        """Host-stream spelling of :meth:`apply` (concrete numpy values).

        The serving fallback (DESIGN.md §12): same SwiGLU dataflow, every
        matmul through the host product stream via
        :meth:`SparseMatmul.apply_values_host`.  ``x`` is ``[T, D]`` or a
        batch ``[B, T, D]``; returns float32 numpy.
        """
        x = np.asarray(x, np.float32)
        if x.ndim == 3:
            return np.stack([self.apply_host(params, xb) for xb in x])
        xt = x.T                                   # [D, T]
        g = self.gate.apply_values_host(params["gate"], xt)
        u = self.up.apply_values_host(params["up"], xt)
        h = (g / (1.0 + np.exp(-g))) * u           # numpy silu
        return self.down.apply_values_host(params["down"], h).T

    def __call__(self, x):
        """x [T, D] -> [T, D], or a batch [B, T, D] -> [B, T, D].

        A 3-D input runs the batched path: one vmapped kernel launch per
        matrix for the whole batch, replacing the caller-side per-sequence
        loop (the inner loop of batched serving).
        """
        if x.ndim == 3:
            xt = jnp.swapaxes(x, 1, 2)             # [B, D, T]
            h = jax.nn.silu(self.gate.batched(xt)) * self.up.batched(xt)
            return jnp.swapaxes(self.down.batched(h), 1, 2)
        xt = x.T                                   # [D, T]
        h = jax.nn.silu(self.gate(xt)) * self.up(xt)
        return self.down(h).T

    @property
    def flops_per_token(self) -> int:
        return (self.gate.flops_per_col + self.up.flops_per_col
                + self.down.flops_per_col)


# ---------------------------------------------------------------------------
# serving integration (DESIGN.md §12)
# ---------------------------------------------------------------------------


def sparsify_ffn_params(cfg, params, *, keep_density=0.5,
                        stream_limit: int | None = None):
    """Convert every scanned FFN sub-layer of a model to ``path="spgemm"``.

    The serving-engine entry point (DESIGN.md §12): walks the model's
    super-block table and, for each sub-layer carrying a dense SwiGLU
    ``ffn`` subtree (kinds ``attn_ffn`` / ``attn_ffn_cross`` / ...),
    replaces its stacked ``[n_rep, d_in, d_out]`` weight leaves with CSC
    value stacks ``{"gate"/"up"/"down": [n_rep, nnz]}`` on a pattern
    *shared across the scanned reps* (:meth:`SparseMatmul
    .from_shared_pattern` — one mask per matrix, so the scan body traces
    once and all reps replay one cached plan).  MoE and shared-table
    sub-layers are left dense.

    Returns ``(new_params, overlay)``: ``new_params`` is the params pytree
    with the sparse value stacks spliced in, ``overlay`` maps sub-layer
    keys ``"l{i}"`` to the pattern-holding :class:`SparseFFN` that
    ``decode_step(..., sparse_ffn=overlay)`` (and ``ServeEngine``) applies
    with each rep's values.  Raises if the config has no scanned FFN
    sub-layer to convert.
    """
    from repro.models.blocks import superblock_table

    _, kinds, _, _ = superblock_table(cfg)
    overlay = {}
    new_blocks = dict(params["blocks"])
    for i, _kind in enumerate(kinds):
        li = f"l{i}"
        sub = params["blocks"].get(li, {})
        if "ffn" not in sub:
            continue
        fp = sub["ffn"]

        def shared(name):
            w = np.asarray(fp[name]["w"])        # [R, d_in, d_out]
            return SparseMatmul.from_shared_pattern(
                w.transpose(0, 2, 1),            # -> W @ x orientation
                keep_density=keep_density, stream_limit=stream_limit)

        gate, gv = shared("gate")
        up, uv = shared("up")
        down, dv = shared("down")
        overlay[li] = SparseFFN(gate, up, down)
        new_blocks[li] = dict(sub, ffn={"gate": gv, "up": uv, "down": dv})
    if not overlay:
        raise ValueError(
            f"config {cfg.name!r} (family {cfg.family!r}) has no scanned "
            "dense-FFN sub-layer to convert to path='spgemm'")
    return dict(params, blocks=new_blocks), overlay


def densify_ffn_params(cfg, params, overlay):
    """Inverse view of :func:`sparsify_ffn_params` for reference checks.

    Scatters each overlay matrix's ``[n_rep, nnz]`` value stacks back into
    dense ``[n_rep, d_in, d_out]`` weight leaves (zeros at pruned
    positions), so a plain dense ``decode_step`` over the result is the
    numerical oracle for the sparse decode path (tests, and the honesty
    check in ``benchmarks/serving_spgemm.py``).
    """
    new_blocks = dict(params["blocks"])
    for li, sffn in overlay.items():
        vals = params["blocks"][li]["ffn"]
        dense = {}
        for name, mat in (("gate", sffn.gate), ("up", sffn.up),
                          ("down", sffn.down)):
            c = mat.w_csc
            rows = np.asarray(c.row_indices)[: c.nnz]
            cols = np.repeat(np.arange(c.shape[1], dtype=np.int32),
                             np.diff(np.asarray(c.col_ptr)))
            v = np.asarray(vals[name], np.float32)        # [R, nnz]
            w = np.zeros((v.shape[0],) + tuple(c.shape), np.float32)
            w[:, rows, cols] = v
            # back to the param table's [R, d_in, d_out] orientation
            dense[name] = {"w": jnp.asarray(w.transpose(0, 2, 1))}
        new_blocks[li] = dict(new_blocks[li], ffn=dense)
    return dict(params, blocks=new_blocks)
