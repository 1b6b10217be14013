"""Partition rules + sharded score/train step factories.

Megatron-style layout for the trace models (odigos_tpu.models), expressed
as a ``match_partition_rules``-style table of (regex, PartitionSpec) pairs
over the mesh from parallel.mesh:

* attention q/k/v kernels (d_model, n_heads, head_dim): heads on "model"
* attention out kernel (n_heads, head_dim, d_model): heads on "model"
* encoder mlp up kernel (d_model, d_ff): d_ff on "model"; down transposed
* decoder block: q/k/v (d_model, heads x head_dim) and gate/up (d_model,
  d_ff) columns on "model"; out and down rows on "model"; RMS norms
  replicated
* routed block: q/k/v/out as the decoder block's; the router and the
  three expert kernels (n_experts, fan_in, fan_out) whole on every device,
  and a mesh with a "model" axis over 1 refused (``WHOLE_ONLY``) where the
  plan is built. Under ``mesh {data: n}`` each device routes its own rows
  (``layers.each_device_its_rows``, which asks for the mesh the plan
  traces its call inside): held by a test on four virtual CPU devices
  and a compile for a described v5e 2 x 2, not yet run on four chips
* latent routed block: the two expansions out of the latents (q_b_proj,
  kv_b_proj: columns are heads) and the shared expert's gate and up
  columns on "model", out / down / shared down rows on "model"; the two
  compressions (q_a_proj, kv_a_proj: every head reads the whole latent)
  and the latent norms replicated; the router, its selection bias and the
  expert kernels whole on every device, as the routed block's
* autoencoder decoder ffn + wide vocab heads: d_ff / vocab on "model"
* embedding tables + layernorms + small heads: replicated
* batch (packed-row / trace) axis of inputs: "data"

XLA inserts the all-reduces (psum over "model" after attention-out and
mlp-down) — we only annotate placements, per the scaling-book recipe cited
in the build brief. ``compile_plan`` graduates the rules from a demo
helper into the ScoringEngine's device layer: one plan per (model, mesh)
holding the rule-matched param placements and the explicit in/out
shardings of the packed scoring call.

Numerics contract: "data"-axis sharding is BITWISE identical to single
device (rows are independent; each shard runs the same per-row program).
A "model" axis reassociates the contraction reductions (partial matmul +
psum), so dp×tp parity is ULP-level (~1e-7 at fp32), never bitwise — the
parity suite and the multichip bench assert bitwise on dp and tight
allclose once tp > 1.
"""

from __future__ import annotations

import re
from typing import Any, Callable, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import jitstats
from .mesh import mesh_key

# see models/transformer.py: every jitted scoring/training entry point
# declares its recompile-bounding strategy (package hygiene test)
SHAPE_BUCKETING = {
    "make_sharded_score_fn": "delegates to model.score_spans — leading axis "
                             "padded to a data-axis multiple by "
                             "_shard_inputs on top of the engine bucketing",
    "make_sharded_packed_score_fn": "delegates to compile_plan — row "
                                    "axis bucketed by the engine's ladder "
                                    "(rungs lcm-aligned to the data axis)",
    "make_sharded_train_step": "training loop feeds fixed (batch, L) "
                               "shapes from data.py batching; one compile "
                               "per run",
    "compile_plan": "packed row axis bucketed by the engine's "
                    "BucketLadder (rungs lcm-aligned to the data axis, "
                    "warmed once per mesh shape); L/C fixed by the model "
                    "config",
    "packed_score": "the jit compile_plan builds — same row-axis "
                    "bucketing as compile_plan (one executable per "
                    "warmed rung per mesh shape)",
}

# Partition-spec declaration per sharded entry point (package-hygiene
# lint, ISSUE 7 satellite): any factory in parallel/ that jits or places
# arrays under a mesh must say where each tensor class lands — an
# undeclared sharded jit silently runs replicated and burns dp-fold HBM.
PARTITION_SPECS = {
    "compile_plan": "params via PARTITION_RULES (heads/d_ff/vocab on "
                    "'model', rest replicated); packed inputs and scores "
                    "P('data', ...) on rows",
    "make_sharded_score_fn": "params via PARTITION_RULES; (T, L, *) "
                             "inputs P('data', ...) on traces",
    "make_sharded_packed_score_fn": "alias of compile_plan (packed rows "
                                    "on 'data', params by rule table)",
    "make_sharded_train_step": "params/grads/opt state via caller's "
                               "shard_variables placement; batch inputs "
                               "P('data', ...); loss replicated",
    "shard_variables": "rule table (PARTITION_RULES) or explicit spec_fn; "
                       "non-dividing or absent axes fall back to "
                       "replication",
    "packed_score": "the compiled packed-score jit: params by committed "
                    "rule-table placement, (R, L, *) inputs and (R, L) "
                    "scores pinned P('data', ...)",
    "shard_inputs": "batch-leading arrays placed P('data', ...), leading "
                    "dim padded to a data-axis multiple (pad rows stay "
                    "masked)",
}


# ------------------------------------------------------ partition rules

# parameters that only ever sit whole on a device: a plan whose mesh has
# a "model" axis over 1 refuses a model that holds any (the rule that
# places them, below, says why)
WHOLE_ONLY = (r"block_\d+/(router_bias|(router|experts_(gate|up|down))"
              r"/kernel)$")

# First-match-wins (re.search over the '/'-joined param path). The
# catch-all replicates embeddings, norms, biases, and small heads —
# sharding those only buys per-call collectives. Param names cover BOTH
# sequence models: flax auto-names (Attention_N, block_N/Dense_0 up /
# Dense_1 down) plus the autoencoder's decoder ffn and wide vocab heads.
PARTITION_RULES: tuple[tuple[str, P], ...] = (
    (r"Attention_\d+/(query|key|value)/kernel$", P(None, "model", None)),
    (r"Attention_\d+/out/kernel$", P("model", None, None)),
    (r"block_\d+/Dense_0/kernel$", P(None, "model")),  # mlp up: d_ff cols
    (r"block_\d+/Dense_1/kernel$", P("model", None)),  # mlp down: d_ff rows
    # the decoder block's seven kernels, all 2D: q/k/v columns are heads,
    # gate/up columns d_ff; out and down contract over them
    (r"block_\d+/(q|k|v|gate|up)_proj/kernel$", P(None, "model")),
    (r"block_\d+/(o|down)_proj/kernel$", P("model", None)),
    # the latent routed block: a dense layer's gate/up/down_proj and every
    # layer's o_proj fall under the two rules above. The expansions out of
    # the latents have a head's columns side by side, as q/k/v_proj have,
    # and the shared expert is a SwiGLU like the decoder block's; the
    # compressions into the latents (q_a_proj, kv_a_proj) are read whole
    # by every head and stay replicated, with the latent norms
    (r"block_\d+/((q|kv)_b_proj|shared_(gate|up))/kernel$",
     P(None, "model")),
    (r"block_\d+/shared_down/kernel$", P("model", None)),
    (r"block_\d+/(q|kv)_a_proj/kernel$", P()),
    # the routed block's q/k/v/o_proj are 2D and fall under the two rules
    # above. Its router and its expert kernels (experts_gate, experts_up:
    # (n_experts, d_model, d_expert); experts_down: (n_experts, d_expert,
    # d_model)) are replicated: the grouped products run over rows sorted
    # by expert, a data-dependent split that no static spec of d_expert
    # or of the expert axis divides, and an expert axis over "model" needs
    # a layer told which experts it holds and an exchange of spans
    # (ROADMAP C5). The latent routed block's selection bias goes with its
    # router. WHOLE_ONLY below refuses a "model" axis for them rather than
    # replicating 95% of the parameters in silence.
    (WHOLE_ONLY, P()),
    (r"dec_ff1/kernel$", P(None, "model")),            # autoencoder decoder
    (r"dec_ff2/kernel$", P("model", None)),
    (r"(service|name)_head/kernel$", P(None, "model")),  # wide vocab heads
    (r"", P()),  # embeddings, norms, biases, small heads: replicated
)


def _param_name(path: tuple) -> str:
    """The '/'-joined parameter path the rules are matched against."""
    return "/".join(str(k.key) for k in path)


def refuse_unplaceable(variables: Any, mesh: Mesh) -> None:
    """Raise where the mesh asks the rule table for a placement it does
    not have: a "model" axis over 1 and a ``WHOLE_ONLY`` parameter."""
    tp = int(mesh.shape.get("model", 1))
    if tp <= 1:
        return
    whole = [name for name in (
        _param_name(path) for path, _
        in jax.tree_util.tree_leaves_with_path(variables))
        if re.search(WHOLE_ONLY, name)]
    if whole:
        raise ValueError(
            f"a mesh with model axis {tp} cannot place {len(whole)} "
            f"parameters (the first: {whole[0]}): PARTITION_RULES holds a "
            f"routed block's router and expert kernels whole on every "
            f"device, since its grouped products split rows by expert at "
            f"run time. mesh {{data: n}} replicates them, each device "
            f"routing its own rows (equal scores on four virtual CPU "
            f"devices and a compile for a v5e 2 x 2; not yet run on four "
            f"chips: PERF.md section 7)")


def match_partition_rules(params: Any,
                          rules: tuple = PARTITION_RULES) -> Any:
    """Pytree of PartitionSpecs per the rule table (the SNIPPETS.md [1]
    idiom): scalars/size-1 leaves never partition; otherwise the first
    rule whose regex matches the '/'-joined path wins. The shipped table
    ends with a catch-all, so every leaf resolves."""
    def spec_for(path, leaf) -> P:
        if getattr(leaf, "ndim", 0) == 0 or np.prod(
                getattr(leaf, "shape", ())) == 1:
            return P()
        name = _param_name(path)
        for rule, spec in rules:
            if re.search(rule, name) is not None:
                return spec
        raise ValueError(f"partition rule not found for param: {name}")

    return jax.tree_util.tree_map_with_path(spec_for, params)


def transformer_param_spec(path: tuple, leaf: Any) -> P:
    """Shape-heuristic fallback (pre-rule-table API, kept for callers
    that shard pytrees with no stable names): q/k/v/out by position,
    any large 2D kernel by its grown dimension."""
    names = [str(p) for p in path]
    joined = "/".join(names)
    ndim = getattr(leaf, "ndim", 0)
    if "attention" in joined or any(n in ("query", "key", "value", "out")
                                    for n in names):
        if any(n in ("query", "key", "value") for n in names) and ndim == 3:
            return P(None, "model", None)  # (d_model, heads, head_dim)
        if "out" in names and ndim == 3:
            return P("model", None, None)  # (heads, head_dim, d_model)
    # mlp: first Dense grows to d_ff (shard cols), second shrinks. Size
    # gate keeps tiny matmuls (span/trace heads, embedder projections)
    # replicated — sharding them only buys per-call collectives.
    if ndim == 2 and names[-1] == "kernel":
        in_dim, out_dim = leaf.shape
        if min(in_dim, out_dim) >= 64:
            if out_dim > in_dim:
                return P(None, "model")
            if in_dim > out_dim:
                return P("model", None)
    return P()  # replicate embeddings, norms, biases, heads


def _guard_spec(spec: P, leaf: Any, mesh: Mesh) -> P:
    """Axes must exist in this mesh and divide the dim; fall back to
    replication when they don't (a pure-"data" DP mesh replicates every
    "model"-sharded param)."""
    for axis_name, dim in zip(spec, getattr(leaf, "shape", ())):
        if axis_name is None:
            continue
        if axis_name not in mesh.shape or dim % mesh.shape[axis_name] != 0:
            return P()
    return spec


def shard_variables(variables: Any, mesh: Mesh,
                    spec_fn: Optional[Callable[[tuple, Any], P]] = None,
                    rules: tuple = PARTITION_RULES) -> Any:
    """Place a variable pytree onto the mesh: by the rule table (default,
    resolved through ``match_partition_rules`` — ONE rule-resolution
    path, so placements can never drift from the specs tests and
    describe surfaces report) or an explicit ``spec_fn(path, leaf)``."""
    if spec_fn is not None:
        def place(path, leaf):
            spec = spec_fn(tuple(k.key for k in path), leaf)
            return jax.device_put(
                leaf, NamedSharding(mesh, _guard_spec(spec, leaf, mesh)))

        return jax.tree_util.tree_map_with_path(place, variables)
    specs = match_partition_rules(variables, rules)
    return jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(
            leaf, NamedSharding(mesh, _guard_spec(spec, leaf, mesh))),
        variables, specs)


def batch_spec(mesh: Mesh) -> P:
    return P("data")


def _shard_inputs(mesh: Mesh, arrays: tuple) -> tuple:
    """Place batch-leading arrays on the data axis, padding the leading dim
    up to a multiple of the data-axis size (mask rows stay False)."""
    dp = mesh.shape["data"]
    sharded = []
    for a in arrays:
        n = a.shape[0]
        pad = (-n) % dp
        if pad:
            widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
            a = np.pad(np.asarray(a), widths)
        sharded.append(jax.device_put(
            a, NamedSharding(mesh, P("data", *([None] * (a.ndim - 1))))))
    return tuple(sharded)


# ------------------------------------------------------- scoring plans


def _packed_score_jit(model, mesh: Mesh):
    """Compile the packed-scoring fn for one (model, mesh) pairing:
    params ride their committed placement (``place_variables`` has
    already device_put them per the rule table — an explicit in_sharding
    would just restate it); inputs and output are pinned to "data" so
    the call NEVER silently runs replicated even if a caller hands host
    arrays."""
    impl = getattr(model, "_score_packed_impl", None)
    if impl is None:
        return None
    row = NamedSharding(mesh, P("data", None))
    row3 = NamedSharding(mesh, P("data", None, None))
    out = row
    if getattr(model, "score_packed_counted", None) is not None:
        # the model's counted entry: scores on "data", the call's counts
        # (scalars) on every device
        impl = model._score_packed_counted_impl
        out = (row, NamedSharding(mesh, P()))
    return jitstats.track_jit(
        f"parallel.plan.score_packed[{mesh_key(mesh)}]",
        jax.jit(impl,
                in_shardings=(None, row3, row3, row, row),
                out_shardings=out))


class ScoringPlan:
    """One (model, mesh) pairing compiled for serving — the engine's
    device layer (ISSUE 7 tentpole, the ``compile_step_with_plan``
    pattern from SNIPPETS.md [3]).

    Owns: the rule-matched param PartitionSpecs, an identity-cached
    ``place_variables`` (params move to device once per weight pytree,
    not per call), the packed scoring fn jitted with EXPLICIT in/out
    shardings (inputs on "data", scores on "data", params per rules),
    and a propagation-sharded ``score_spans`` for the
    sequence (autoencoder) route. Neither entry blocks on the device:
    the engine harvests against the next in-flight call.
    """

    def __init__(self, model: Any, mesh: Mesh,
                 rules: tuple = PARTITION_RULES, variables: Any = None):
        if variables is not None:    # refuse at once, not at first call
            refuse_unplaceable(variables, mesh)
        self.model = model
        self.mesh = mesh
        self.rules = rules
        self.dp = int(mesh.shape.get("data", 1))
        self.tp = int(mesh.shape.get("model", 1))
        self.key = mesh_key(mesh)
        # cache the placed pytree of the last-seen weights. Keyed by id()
        # ALONE this is unsound — a GC'd pytree's address can be reused
        # and serve stale weights — so the cache holds a strong ref to
        # the source pytree and revalidates by identity against it.
        self._cache: dict[str, Any] = {"source": None, "placed": None}
        self._packed_jit = _packed_score_jit(model, mesh)

    def param_specs(self, variables: Any) -> Any:
        """Rule-matched PartitionSpec pytree for a weight pytree
        (mesh-guarded: non-dividing or absent axes replicate) — what
        ``place_variables`` commits, exposed for tests and describe
        surfaces."""
        specs = match_partition_rules(variables, self.rules)
        flat_v = jax.tree_util.tree_leaves(variables)
        flat_s, treedef = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: isinstance(x, P))
        guarded = [_guard_spec(s, v, self.mesh)
                   for s, v in zip(flat_s, flat_v)]
        return jax.tree_util.tree_unflatten(treedef, guarded)

    def place_variables(self, variables: Any) -> Any:
        """Device placement per the rule table, cached by identity."""
        if self._cache["source"] is not variables:
            self._cache["source"] = variables
            self._cache["placed"] = shard_variables(
                variables, self.mesh, rules=self.rules)
        return self._cache["placed"]

    def score_packed(self, variables, categorical, continuous, segments,
                     positions):
        """Sharded packed scoring; returns the (R, L) device array
        WITHOUT blocking (the engine's harvest stage fetches it)."""
        return self.score_packed_counted(variables, categorical, continuous,
                                         segments, positions)[0]

    def score_packed_counted(self, variables, categorical, continuous,
                             segments, positions):
        """``score_packed`` and what the model counted on the device of
        the call (None for a model that counts nothing), neither waited
        for."""
        R = np.asarray(segments).shape[0]
        if R % self.dp:
            raise ValueError(
                f"packed rows {R} not divisible by data axis {self.dp}; "
                f"the engine's BucketLadder aligns rungs to the mesh — "
                f"pad rows with ladder.round_rows")
        v = self.place_variables(variables)
        categorical, continuous, segments, positions = _shard_inputs(
            self.mesh, (categorical, continuous, segments, positions))
        # traced inside the mesh: a model whose rows each device works
        # alone (the routed block's grouped products) asks which mesh
        # splits them
        with jax.set_mesh(self.mesh):
            out = self._packed_jit(v, categorical, continuous, segments,
                                   positions)
        return out if isinstance(out, tuple) else (out, None)

    def placed_bytes(self) -> int:
        """Bytes held on device by the cached placed weight pytree (the
        plan's staging footprint — 0 until ``place_variables`` ran).
        Read by the DeviceRuntimeCollector's device-table gauges
        (ISSUE 20): the fused route's resident footprint is tables +
        whatever each live plan keeps placed."""
        placed = self._cache.get("placed")
        if placed is None:
            return 0
        total = 0
        for leaf in jax.tree_util.tree_leaves(placed):
            total += int(getattr(leaf, "nbytes", 0) or 0)
        return total

    def score_spans(self, variables, categorical, continuous, mask):
        """Sequence-route scoring (autoencoder): params per rules, inputs
        on "data"; the model's own jit propagates the placements and XLA
        inserts the collectives. Non-blocking device results."""
        v = self.place_variables(variables)
        categorical, continuous, mask = _shard_inputs(
            self.mesh, (categorical, continuous, mask))
        return self.model.score_spans(v, categorical, continuous, mask)


def compile_plan(model, mesh: Mesh, *, rules: tuple = PARTITION_RULES,
                 variables: Any = None) -> ScoringPlan:
    """Build the (model, mesh) serving plan; given the weights, refuse
    at once a mesh that cannot place them."""
    return ScoringPlan(model, mesh, rules=rules, variables=variables)


# ------------------------------------------------ legacy factory seams


def make_sharded_score_fn(model, mesh: Mesh):
    """Data/tensor-parallel scoring: variables pre-sharded per the rules,
    inputs split on "data". Returns fn(variables, cat, cont, mask) ->
    (span_scores, trace_scores) gathered to host-replicated arrays."""

    def score(variables, cat, cont, mask):
        n = np.asarray(mask).shape[0]
        cat, cont, mask = _shard_inputs(mesh, (cat, cont, mask))
        # model.score_spans is jitted; XLA propagates the dp/tp shardings
        # from argument placements and inserts the collectives
        span_p, trace_p = model.score_spans(variables, cat, cont, mask)
        return np.asarray(span_p)[:n], np.asarray(trace_p)[:n]

    return score


def make_sharded_train_step(model, tx, mesh: Mesh):
    """Full sharded train step (used by __graft_entry__.dryrun_multichip and
    train.loop): grads computed under dp(batch) x tp(params) sharding; optax
    update applied in the same placement; loss replicated.
    """

    @jax.jit
    def step(variables, opt_state, cat, cont, mask, span_labels, trace_labels):
        loss, grads = jax.value_and_grad(model.loss_fn)(
            variables, cat, cont, mask, span_labels, trace_labels)
        updates, opt_state = tx.update(grads, opt_state, params=variables)
        import optax

        variables = optax.apply_updates(variables, updates)
        return variables, opt_state, loss

    def run(variables, opt_state, cat, cont, mask, span_labels, trace_labels):
        cat, cont, mask, span_labels, trace_labels = _shard_inputs(
            mesh, (cat, cont, mask, span_labels, trace_labels))
        return step(variables, opt_state, cat, cont, mask, span_labels,
                    trace_labels)

    return run


def make_sharded_packed_score_fn(model, mesh: Mesh, block: bool = True):
    """Data-parallel **packed** scoring (BASELINE config #5: DP across
    v5e-8) — kept as the thin pre-plan API over ``compile_plan``.

    ``block=False`` returns the (R, L) device array without the host
    fetch: the pipelined engine harvests it against the *next* in-flight
    call so the transfer overlaps device execution. R is unpadded (the
    divisibility check guarantees it), so no trailing-slice is needed.
    """
    plan = compile_plan(model, mesh)

    def score(variables, cat, cont, segments, positions):
        R = np.asarray(segments).shape[0]
        span_p = plan.score_packed(variables, cat, cont, segments,
                                   positions)
        if not block:
            return span_p
        return np.asarray(span_p)[:R]

    return score
