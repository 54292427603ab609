"""Compile-the-real-train-step glue for the analyzers.

This is the only module in ``midgpt_tpu.analysis`` that imports jax: it
builds the mesh/optimizer/state for a named config, compiles the actual
``make_train_step`` (optionally shrunk to audit size), and hands the
post-optimization HLO to the jax-free parser/rules/cost layers.

Shrinking (``shrink_for_audit``) keeps the mesh axes, sharding rules and
code paths of the full config but cuts layers/vocab/sequence so the audit
compiles in seconds on the 8-device CPU virtual mesh — the partitioner
decisions the rules check are per-layer-shape, not per-depth.
"""

from __future__ import annotations

import contextlib
import dataclasses
import typing as tp

import numpy as np

from midgpt_tpu.analysis import hlo as hlo_mod
from midgpt_tpu.analysis.rules import Report, StepAnalysis, rules_for_config
from midgpt_tpu.config import ExperimentConfig, get_config

# the input-batch layout every entry point feeds the step with
# (train.py batch_spec); logical, resolved against the mesh axis names
BATCH_SPEC_AXES = (None, ("replica", "fsdp"), "sequence")


def shrink_for_audit(
    cfg: ExperimentConfig,
    *,
    n_layer: int = 2,
    block: int = 256,
    vocab: int = 1024,
    batch: int = 8,
) -> ExperimentConfig:
    """Audit-sized variant of ``cfg``: same mesh axes, sharding rules and
    code paths (incl. the chunked-loss path via ``loss_chunk=block//2``),
    shrunk to compile fast on the CPU virtual mesh."""
    model = dataclasses.replace(
        cfg.model,
        n_layer=n_layer,
        block_size=block,
        vocab_size=vocab,
        remat="none",
        scan_unroll=1,
    )
    return dataclasses.replace(
        cfg,
        model=model,
        batch_size=batch,
        g_accum_iters=1,
        loss_chunk=block // 2,  # 2 chunks: keeps the chunked-loss path
    )


@contextlib.contextmanager
def override_logical_rules(overrides: tp.Optional[tp.Mapping[str, tp.Any]]):
    """Temporarily rewrite entries of the activation logical-rule table
    (``parallel.sharding.DEFAULT_LOGICAL_RULES``).

    This is the fault-injection hook: mapping ``batch`` to ``None``
    reproduces the classic opaque-boundary trap (the partitioner gathers
    the full batch onto every device), which the ``no-batch-allgather``
    rule must catch. Also usable for what-if cost reports.
    """
    if not overrides:
        yield
        return
    from midgpt_tpu.parallel import sharding

    old = sharding.DEFAULT_LOGICAL_RULES
    unknown = set(overrides) - set(old)
    assert not unknown, f"unknown logical axes {sorted(unknown)}"
    patched = dict(old)
    patched.update(overrides)
    sharding.DEFAULT_LOGICAL_RULES = patched  # type: ignore[assignment]
    try:
        yield
    finally:
        sharding.DEFAULT_LOGICAL_RULES = old  # type: ignore[assignment]


def compile_train_step(
    cfg: ExperimentConfig,
    logical_overrides: tp.Optional[tp.Mapping[str, tp.Any]] = None,
):
    """Compile the real donated train program for ``cfg`` on the current
    backend's devices — the per-step jit when ``steps_per_dispatch == 1``,
    the fused K-step ``make_train_window`` scan otherwise (so the audit
    sees exactly the program the trainer launches, incl. donation across
    the whole window). Returns ``(hlo_text, mesh, donated_leaves)``."""
    import jax
    from jax.sharding import PartitionSpec as P

    from midgpt_tpu.parallel.mesh import create_mesh
    from midgpt_tpu.parallel.sharding import make_global_array
    from midgpt_tpu.train import (
        init_state,
        make_optimizer,
        make_train_step,
        make_train_window,
    )

    mesh = create_mesh(cfg.mesh)
    tx, _ = make_optimizer(cfg)
    k = cfg.steps_per_dispatch
    with override_logical_rules(logical_overrides):
        # abstract: sharded ShapeDtypeStructs, not device buffers — the
        # audit lowers/compiles but never executes, so full-size configs
        # (bench.py's comms rung) don't pay params + Adam moments in HBM
        state = init_state(cfg, mesh, tx, jax.random.PRNGKey(0), abstract=True)
        b = cfg.microbatch_size
        t = cfg.model.block_size
        if k > 1:
            step = make_train_window(cfg, tx, mesh, k)
            x = np.zeros((k, cfg.g_accum_iters, b, t), np.int32)
            spec = P(None, *BATCH_SPEC_AXES)
        else:
            step = make_train_step(cfg, tx, mesh)
            x = np.zeros((cfg.g_accum_iters, b, t), np.int32)
            spec = P(*BATCH_SPEC_AXES)
        xg = make_global_array(x, mesh, spec)
        hlo = step.lower(
            state, xg, xg, jax.random.PRNGKey(1)
        ).compile().as_text()
    donated_leaves = len(jax.tree.leaves(state))
    return hlo, mesh, donated_leaves


def compile_eval_sweep(cfg: ExperimentConfig, n_eval: int = 3):
    """Compile the stacked-batch eval sweep (``make_eval_step``) for
    ``cfg``. Returns ``(hlo_text, mesh)``."""
    import jax
    from jax.sharding import PartitionSpec as P

    from midgpt_tpu.parallel.mesh import create_mesh
    from midgpt_tpu.parallel.sharding import make_global_array
    from midgpt_tpu.train import init_state, make_eval_step, make_optimizer

    mesh = create_mesh(cfg.mesh)
    tx, _ = make_optimizer(cfg)
    state = init_state(cfg, mesh, tx, jax.random.PRNGKey(0), abstract=True)
    sweep = make_eval_step(cfg, mesh)
    b = cfg.microbatch_size
    t = cfg.model.block_size
    x = np.zeros((n_eval, b, t), np.int32)
    xg = make_global_array(x, mesh, P(*BATCH_SPEC_AXES))
    hlo = sweep.lower(state.params, xg, xg).compile().as_text()
    return hlo, mesh


def analyze_train_step(
    cfg: ExperimentConfig,
    *,
    shrink: bool = True,
    logical_overrides: tp.Optional[tp.Mapping[str, tp.Any]] = None,
) -> StepAnalysis:
    """Compile ``cfg``'s train step and wrap it in a :class:`StepAnalysis`
    ready for rules/cost evaluation."""
    audit_cfg = shrink_for_audit(cfg) if shrink else cfg
    hlo, mesh, donated = compile_train_step(audit_cfg, logical_overrides)
    return StepAnalysis.from_text(
        hlo,
        hlo_mod.MeshInfo.from_mesh(mesh, num_slices=audit_cfg.mesh.num_slices),
        global_batch=audit_cfg.microbatch_size,
        block=audit_cfg.model.block_size,
        donated_leaves=donated,
    )


def audit_config(
    name_or_cfg: tp.Union[str, ExperimentConfig],
    *,
    shrink: bool = True,
    logical_overrides: tp.Optional[tp.Mapping[str, tp.Any]] = None,
) -> tp.Tuple[StepAnalysis, Report, tp.Dict[str, tp.Any]]:
    """One-call audit: compile, evaluate the config's ruleset, build the
    cost report. Returns ``(analysis, rule_report, cost_report)``."""
    from midgpt_tpu.analysis.cost import cost_report

    cfg = (
        get_config(name_or_cfg)
        if isinstance(name_or_cfg, str)
        else name_or_cfg
    )
    analysis = analyze_train_step(
        cfg, shrink=shrink, logical_overrides=logical_overrides
    )
    report = rules_for_config(cfg, analysis.mesh).evaluate(analysis)
    return analysis, report, cost_report(analysis)


def parse_mesh_shape(spec: tp.Optional[str]) -> tp.Optional[tp.Dict[str, int]]:
    """``"tp=2,replica=2"`` -> ``{"tensor": 2, "replica": 2}`` (the
    --mesh-shape CLI flag for the sharded serving audits). Accepted keys:
    ``tp``/``tensor``, ``dp``/``replica``, ``fsdp``. jax-free."""
    if not spec:
        return None
    alias = {"tp": "tensor", "tensor": "tensor", "dp": "replica",
             "replica": "replica", "fsdp": "fsdp"}
    out: tp.Dict[str, int] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, val = part.partition("=")
        key = alias.get(name.strip())
        try:
            size = int(val.strip())
        except ValueError:
            size = 0
        if key is None or size < 1:
            raise ValueError(
                f"bad --mesh-shape entry {part!r} (want tp=N / replica=N "
                "/ fsdp=N with N >= 1)"
            )
        out[key] = size
    return out or None


def serving_payload_shapes(
    model_cfg,
    *,
    slots: int,
    page_size: int,
    num_pages: int,
    rows: tp.Iterable[int],
) -> tp.FrozenSet[tp.Tuple[int, ...]]:
    """Every FULL (unsharded) shape a serving program's pool/page-gather
    payload can take at one audited geometry — what the
    ``no-batch-allgather-in-page-gather`` rule is parameterized with. An
    all-gather producing one of these in the SPMD-partitioned HLO means
    a KV-head-sharded buffer was regathered to all heads. ``rows`` lists
    the per-dispatch row counts the program writes (decode window K,
    prefill chunk length, verify spec_len + 1)."""
    from midgpt_tpu.serving.paged import pages_needed

    l = model_cfg.n_layer
    hkv = model_cfg.kv_heads
    c = model_cfg.head_dim
    ps = page_size
    pmax = pages_needed(model_cfg.block_size, page_size)
    shapes: tp.Set[tp.Tuple[int, ...]] = {
        (l, num_pages, hkv, c, ps),  # the pool itself
        (num_pages, hkv, c, ps),  # one layer's pool
        (slots, pmax, hkv, c, ps),  # block-table-gathered pages
        (slots, hkv, c, pmax * ps),  # the reshaped logical KV view
    }
    for r in rows:
        shapes.add((l, slots, hkv, r, c))  # stacked recent/row buffers
        shapes.add((slots, hkv, r, c))  # one layer's rows
    return frozenset(shapes)


def _serving_audit_setup(cfg: ExperimentConfig, *, slots: int,
                         page_size: int, shrink: bool,
                         quant: bool = False,
                         kv_quant: bool = False,
                         mesh_shape: tp.Optional[tp.Mapping[str, int]] = None):
    """Shared geometry for the three serving audits (decode window +
    prefill chunk + speculative verify): audit-shrunk model config,
    bf16-cast model, page pool and slot logits. ONE definition so the
    compiled programs can never silently audit different geometries.
    ``quant=True`` converts the model to the int8 quantized serving
    pytree (midgpt_tpu.quant) and additionally returns its weight-matrix
    shapes — what the no-dequant-materialization rule is parameterized
    with (empty when quant is off).

    ``mesh_shape`` (e.g. ``{"tensor": 2}``, the --mesh-shape CLI flag)
    compiles the SHARDED programs instead: a multi-device mesh over the
    first prod(axes) devices, model committed per GPT_PARAM_RULES (incl.
    the QuantLinear scale rules), pool KV-head-sharded, logits
    vocab-sharded — exactly how ``ServingEngine(mesh=...)`` places them,
    so the audit sees the partitioned HLO the sharded engine launches.
    The returned ``prog_mesh`` is the mesh to hand the program factories
    (None for the classic single-chip audit); with quant the returned
    weight shapes are the per-SHARD local shapes (what the partitioned
    module actually contains)."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from midgpt_tpu.config import MeshConfig
    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.parallel.mesh import create_mesh
    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.serving.paged import PagedKVPool, pages_needed

    model_cfg = cfg.model
    if shrink:
        model_cfg = _dc.replace(
            model_cfg, n_layer=2, block_size=256, vocab_size=1024,
            remat="none", scan_unroll=1,
        )
    axes = {"replica": 1, "fsdp": 1, "sequence": 1, "tensor": 1}
    if mesh_shape:
        unknown = set(mesh_shape) - set(axes)
        assert not unknown, f"unknown serving mesh axes {sorted(unknown)}"
        axes.update(mesh_shape)
    n_dev = 1
    for v in axes.values():
        n_dev *= v
    assert n_dev <= len(jax.devices()), (
        f"mesh shape {axes} needs {n_dev} devices, have "
        f"{len(jax.devices())}"
    )
    mesh = create_mesh(MeshConfig(**axes), devices=jax.devices()[:n_dev])
    model = cast_floating(GPT.init(jax.random.PRNGKey(0), model_cfg), jnp.bfloat16)
    if quant:
        from midgpt_tpu.quant import quantize_model

        model = quantize_model(model)
    prog_mesh = None
    pmax = pages_needed(model_cfg.block_size, page_size)
    if mesh_shape:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from midgpt_tpu.models.gpt import GPT_PARAM_RULES
        from midgpt_tpu.parallel.sharding import param_shardings

        tp_sz = axes["tensor"]
        assert model_cfg.kv_heads % tp_sz == 0, (
            f"tensor={tp_sz} must divide kv_heads {model_cfg.kv_heads}"
        )
        assert model_cfg.vocab_size % tp_sz == 0, (
            f"tensor={tp_sz} must divide vocab {model_cfg.vocab_size}"
        )
        model = jax.device_put(
            model, param_shardings(mesh, model, GPT_PARAM_RULES)
        )
        pool = PagedKVPool.init(
            model_cfg, slots * pmax, page_size, mesh=mesh,
            kv_quant="int8" if kv_quant else None,
        )
        logits = jax.device_put(
            jnp.zeros((slots, model_cfg.vocab_size), jnp.float32),
            NamedSharding(mesh, P(None, "tensor")),
        )
        prog_mesh = mesh
    else:
        pool = PagedKVPool.init(
            model_cfg, slots * pmax, page_size,
            kv_quant="int8" if kv_quant else None,
        )
        logits = jnp.zeros((slots, model_cfg.vocab_size), jnp.float32)
    wshapes: tp.FrozenSet[tp.Tuple[int, ...]] = frozenset()
    if quant:
        from midgpt_tpu.quant import quant_weight_shapes

        # after device_put: sharded leaves yield per-shard local shapes
        wshapes = quant_weight_shapes(model)
    return model_cfg, mesh, model, pmax, pool, logits, wshapes, prog_mesh


def serving_stream_keys(model, pool, logits) -> tp.Dict[str, tp.FrozenSet]:
    """(dtype, shape) classification keys for the HBM traffic auditor
    (:func:`midgpt_tpu.analysis.traffic.traffic_report`), built from the
    live trees a serving program was compiled against — so the auditor
    classifies exactly the buffers the program streams, not a guess at
    them. Shard-LOCAL shapes under a mesh (what the partitioned HLO's
    entry interface contains)."""
    import jax

    from midgpt_tpu.analysis.traffic import hlo_dtype

    def local_key(arr) -> tp.Tuple[str, tp.Tuple[int, ...]]:
        sharding = getattr(arr, "sharding", None)
        if sharding is not None and hasattr(sharding, "shard_shape"):
            shape = tuple(int(d) for d in sharding.shard_shape(arr.shape))
        else:
            shape = tuple(int(d) for d in arr.shape)
        return (hlo_dtype(arr.dtype), shape)

    return {
        "weights": frozenset(
            local_key(x) for x in jax.tree.leaves(model)
        ),
        "kv": frozenset(local_key(x) for x in jax.tree.leaves(pool)),
        "logits": frozenset([local_key(logits)]),
    }


def _serving_rules(
    wshapes,
    payload_shapes: tp.Optional[tp.FrozenSet] = None,
    slots: tp.Optional[int] = None,
) -> "RuleSet":
    """The serving-invariant ruleset all three program audits share:
    donation-intact + no-host-sync + no-f64, plus
    no-dequant-materialization when the program was compiled against the
    quantized pytree (``wshapes`` non-empty), plus
    no-batch-allgather-in-page-gather when it was compiled on a sharded
    mesh (``payload_shapes`` given — see serving_payload_shapes)."""
    from midgpt_tpu.analysis.rules import (
        DonationIntact,
        NoDequantMaterialization,
        NoF64,
        NoHostSync,
        NoPageGatherAllGather,
        RuleSet,
    )

    rules = [NoF64(), DonationIntact(), NoHostSync()]
    if wshapes:
        rules.append(NoDequantMaterialization(wshapes))
    if payload_shapes:
        rules.append(NoPageGatherAllGather(payload_shapes, slots or 1))
    return RuleSet(rules)


def _serving_traffic(
    program: str,
    analysis: StepAnalysis,
    stream_keys: tp.Mapping[str, tp.FrozenSet],
    *,
    window_steps: int,
):
    """Build the HBM :class:`~midgpt_tpu.analysis.traffic.TrafficReport`
    for one compiled serving program: entry-interface streams classified
    against the live trees' keys, plus the per-dispatch collective wire
    bytes (sharded geometries) so a pool-payload regather moves a budget
    number, not just an HLO shape."""
    from midgpt_tpu.analysis.traffic import traffic_report

    comms = sum(c.traffic_bytes for c in analysis.collectives)
    return traffic_report(
        analysis.hlo,
        program=program,
        stream_keys=stream_keys,
        window_steps=window_steps,
        comms_bytes=comms,
    )


def compile_decode_window(
    cfg: ExperimentConfig,
    *,
    slots: int = 4,
    window: int = 4,
    page_size: int = 16,
    shrink: bool = True,
    quant: bool = False,
    kv_quant: bool = False,
    layer_scan: str = "off",
    mesh_shape: tp.Optional[tp.Mapping[str, int]] = None,
):
    """Compile the serving engine's fused K-step decode window
    (``midgpt_tpu.serving.make_decode_window``) for ``cfg``'s model —
    the program the engine launches once per K generated tokens. Returns
    ``(hlo_text, mesh, donated_leaves, audited_block_size,
    quant_weight_shapes)`` — the block size is the AUDITED model's
    (shrunk when ``shrink``), which is the geometry the HLO was actually
    compiled at; the weight shapes are empty unless ``quant``.

    Audited for the same two regressions the K-step train window is:
    donation staying intact across the window (pool + logits buffers must
    alias input->output, or every window holds two copies of the KV pool
    in HBM) and no host sync hiding inside it (one stray callback stalls
    all K decode steps per launch). ``quant=True`` compiles the int8
    quantized weight path instead (midgpt_tpu.quant) for the
    no-dequant-materialization rule. ``mesh_shape`` (e.g.
    ``{"tensor": 2}``) compiles the TP-SHARDED program the mesh-aware
    engine launches — head-sharded pool, vocab-sharded logits — and
    additionally returns the full pool payload shapes the
    no-batch-allgather-in-page-gather rule needs."""
    import jax
    import numpy as np_

    from midgpt_tpu.serving.engine import make_decode_window

    model_cfg, mesh, model, pmax, pool, logits, wshapes, prog_mesh = (
        _serving_audit_setup(
            cfg, slots=slots, page_size=page_size, shrink=shrink,
            quant=quant, kv_quant=kv_quant, mesh_shape=mesh_shape,
        )
    )
    window_fn = make_decode_window(
        model, slots=slots, window=window, pmax=pmax,
        rope_len=model_cfg.block_size, mesh=prog_mesh,
        layer_scan=layer_scan,
    )
    i32 = lambda *shape: np_.zeros(shape, np_.int32)  # noqa: E731
    hlo = window_fn.lower(
        model, pool, logits, i32(slots, pmax), i32(slots),
        np_.zeros((slots,), bool), i32(slots), i32(slots), i32(slots),
        i32(slots), jax.random.PRNGKey(1),
    ).compile().as_text()
    donated_leaves = len(jax.tree.leaves((pool, logits)))
    payload = (
        serving_payload_shapes(
            model_cfg, slots=slots, page_size=page_size,
            num_pages=pool.num_pages, rows=(window,),
        )
        if prog_mesh is not None
        else None
    )
    # return the AUDITED model's block size: with shrink it differs from
    # cfg's, and geometry-dependent rules must see the compiled program's
    keys = serving_stream_keys(model, pool, logits)
    return (
        hlo, mesh, donated_leaves, model_cfg.block_size, wshapes, payload,
        keys,
    )


def audit_decode_window(
    name_or_cfg: tp.Union[str, ExperimentConfig],
    *,
    slots: int = 4,
    window: int = 4,
    page_size: int = 16,
    shrink: bool = True,
    quant: bool = False,
    kv_quant: bool = False,
    layer_scan: str = "off",
    mesh_shape: tp.Optional[tp.Mapping[str, int]] = None,
    traffic: bool = False,
):
    """One-call serving audit: compile the fused decode window and check
    the serving invariants (donation-intact, no-host-sync, no-f64 —
    plus no-dequant-materialization when ``quant``, plus
    no-batch-allgather-in-page-gather when ``mesh_shape`` compiles the
    sharded program)."""
    cfg = (
        get_config(name_or_cfg)
        if isinstance(name_or_cfg, str)
        else name_or_cfg
    )
    hlo, mesh, donated, block, wshapes, payload, keys = (
        compile_decode_window(
            cfg, slots=slots, window=window, page_size=page_size,
            shrink=shrink, quant=quant, kv_quant=kv_quant,
            layer_scan=layer_scan, mesh_shape=mesh_shape,
        )
    )
    analysis = StepAnalysis.from_text(
        hlo,
        hlo_mod.MeshInfo.from_mesh(mesh, num_slices=1),
        global_batch=slots,
        block=block,
        donated_leaves=donated,
    )
    report = _serving_rules(wshapes, payload, slots).evaluate(analysis)
    if traffic:
        return analysis, report, _serving_traffic(
            "decode_window", analysis, keys, window_steps=window
        )
    return analysis, report


def compile_prefill_chunk(
    cfg: ExperimentConfig,
    *,
    chunk_len: int = 64,
    page_size: int = 16,
    shrink: bool = True,
    quant: bool = False,
    kv_quant: bool = False,
    layer_scan: str = "off",
    prefill_sp: str = "off",
    mesh_shape: tp.Optional[tp.Mapping[str, int]] = None,
):
    """Compile the serving engine's prefill-chunk program
    (``midgpt_tpu.serving.make_prefill_chunk_program``) — the suffix-only
    prefill the prefix cache and chunked-prefill scheduler dispatch
    between decode windows. Returns ``(hlo_text, mesh, donated_leaves,
    audited_block_size)``.

    Audited for the same serving invariants as the decode window: pool +
    logits donation intact (under chunked prefill a chunk runs between
    every pair of decode windows — an un-aliased pool would double KV
    HBM on the hot path) and no host sync inside the compiled chunk. The
    block table the chunk reads through may alias pages shared with
    other live slots (copy-on-write guarantees they are read-only); the
    compiled program is identical either way, which is exactly why the
    audit covers the sharing case.

    ``prefill_sp="on"`` compiles the SEQUENCE-PARALLEL chunk program
    (``ServingEngine(prefill_sp=...)``): the chunk's replicated row
    segments shard over the 'tensor' axis, so with --traffic the SP
    combine collectives land in ``comms`` — the budget cell for the
    ``prefill_chunk_sp`` program pins that wire traffic (and nothing
    else) via its ``comms_max``. Requires a sharded ``mesh_shape`` with
    tensor > 1 (single-chip SP would be a no-op audit)."""
    import jax
    import numpy as np_

    from midgpt_tpu.serving.engine import make_prefill_chunk_program

    assert prefill_sp in ("off", "on"), prefill_sp
    assert prefill_sp == "off" or (
        mesh_shape and mesh_shape.get("tensor", 1) > 1
    ), "prefill_sp='on' audits need a --mesh-shape with tensor > 1"
    model_cfg, mesh, model, pmax, pool, logits, wshapes, prog_mesh = (
        _serving_audit_setup(
            cfg, slots=4, page_size=page_size, shrink=shrink, quant=quant,
            kv_quant=kv_quant, mesh_shape=mesh_shape,
        )
    )
    assert chunk_len <= model_cfg.block_size, (chunk_len, model_cfg.block_size)
    chunk_fn = make_prefill_chunk_program(
        model, chunk_len=chunk_len, pmax=pmax,
        rope_len=model_cfg.block_size, mesh=prog_mesh,
        layer_scan=layer_scan, prefill_sp=prefill_sp,
    )
    i32 = lambda *shape: np_.zeros(shape, np_.int32)  # noqa: E731
    hlo = chunk_fn.lower(
        model, pool, logits, i32(), i32(1, chunk_len), i32(), i32(),
        i32(pmax),
    ).compile().as_text()
    donated_leaves = len(jax.tree.leaves((pool, logits)))
    payload = (
        serving_payload_shapes(
            model_cfg, slots=1, page_size=page_size,
            num_pages=pool.num_pages, rows=(chunk_len,),
        )
        if prog_mesh is not None
        else None
    )
    keys = serving_stream_keys(model, pool, logits)
    return (
        hlo, mesh, donated_leaves, model_cfg.block_size, wshapes, payload,
        keys,
    )


def audit_prefill_chunk(
    name_or_cfg: tp.Union[str, ExperimentConfig],
    *,
    chunk_len: int = 64,
    page_size: int = 16,
    shrink: bool = True,
    quant: bool = False,
    kv_quant: bool = False,
    layer_scan: str = "off",
    prefill_sp: str = "off",
    mesh_shape: tp.Optional[tp.Mapping[str, int]] = None,
    traffic: bool = False,
):
    """One-call audit of the prefill-chunk program: donation-intact,
    no-host-sync, no-f64 (+ no-dequant-materialization when ``quant``)
    — the CI serving-audit job runs this next to
    :func:`audit_decode_window` so a window containing a mid-window
    prefill chunk (the chunked-prefill steady state) is covered end to
    end."""
    cfg = (
        get_config(name_or_cfg)
        if isinstance(name_or_cfg, str)
        else name_or_cfg
    )
    hlo, mesh, donated, block, wshapes, payload, keys = (
        compile_prefill_chunk(
            cfg, chunk_len=chunk_len, page_size=page_size, shrink=shrink,
            quant=quant, kv_quant=kv_quant, layer_scan=layer_scan,
            prefill_sp=prefill_sp, mesh_shape=mesh_shape,
        )
    )
    analysis = StepAnalysis.from_text(
        hlo,
        hlo_mod.MeshInfo.from_mesh(mesh, num_slices=1),
        global_batch=1,
        block=block,
        donated_leaves=donated,
    )
    report = _serving_rules(wshapes, payload, 1).evaluate(analysis)
    program = (
        "prefill_chunk_sp" if prefill_sp == "on" else "prefill_chunk"
    )
    if traffic:
        return analysis, report, _serving_traffic(
            program, analysis, keys, window_steps=1
        )
    return analysis, report


def compile_verify_program(
    cfg: ExperimentConfig,
    *,
    slots: int = 4,
    spec_len: int = 4,
    page_size: int = 16,
    shrink: bool = True,
    quant: bool = False,
    kv_quant: bool = False,
    layer_scan: str = "off",
    mesh_shape: tp.Optional[tp.Mapping[str, int]] = None,
    temperature: float = 0.0,
    top_k: tp.Optional[int] = None,
):
    """Compile the serving engine's speculative VERIFY program
    (``midgpt_tpu.serving.make_verify_program``) — the single dispatch
    that scores all slots' ``spec_len + 1`` candidate rows against the
    resident pages, decides acceptance (greedy argmax at temperature 0,
    rejection sampling above it — the sampled signature appends only the
    per-slot request seeds and the base PRNG key, so the audited entry
    traffic is the greedy program's plus two control-stream scalars
    per slot), and folds only accepted rows' K/V into the pool. Returns
    ``(hlo_text, mesh, donated_leaves, audited_block_size)``.

    Audited for the same serving invariants as the decode window and the
    prefill chunk: pool + logits donation intact (with speculation on,
    EVERY decode dispatch is a verify dispatch — an un-aliased pool
    would double KV HBM on the hottest path in the engine) and no host
    sync inside the compiled program (drafting is host-side but arrives
    as ordinary inputs; acceptance, watermark, rollback and the page
    write are all in-program — one stray callback would stall every
    speculated token)."""
    import jax
    import numpy as np_

    from midgpt_tpu.serving.engine import make_verify_program

    model_cfg, mesh, model, pmax, pool, logits, wshapes, prog_mesh = (
        _serving_audit_setup(
            cfg, slots=slots, page_size=page_size, shrink=shrink,
            quant=quant, kv_quant=kv_quant, mesh_shape=mesh_shape,
        )
    )
    verify_fn = make_verify_program(
        model, slots=slots, spec_len=spec_len, pmax=pmax,
        rope_len=model_cfg.block_size, temperature=temperature,
        top_k=top_k, mesh=prog_mesh, layer_scan=layer_scan,
    )
    i32 = lambda *shape: np_.zeros(shape, np_.int32)  # noqa: E731
    lower_args = [
        model, pool, logits, i32(slots, pmax), i32(slots),
        np_.zeros((slots,), bool), i32(slots), i32(slots), i32(slots),
        i32(slots, spec_len), i32(slots),
    ]
    if temperature > 0.0:
        lower_args += [
            i32(slots), np_.zeros((2,), np_.uint32),
        ]
    hlo = verify_fn.lower(*lower_args).compile().as_text()
    donated_leaves = len(jax.tree.leaves((pool, logits)))
    payload = (
        serving_payload_shapes(
            model_cfg, slots=slots, page_size=page_size,
            num_pages=pool.num_pages, rows=(spec_len + 1,),
        )
        if prog_mesh is not None
        else None
    )
    keys = serving_stream_keys(model, pool, logits)
    return (
        hlo, mesh, donated_leaves, model_cfg.block_size, wshapes, payload,
        keys,
    )


def audit_verify_program(
    name_or_cfg: tp.Union[str, ExperimentConfig],
    *,
    slots: int = 4,
    spec_len: int = 4,
    page_size: int = 16,
    shrink: bool = True,
    quant: bool = False,
    kv_quant: bool = False,
    layer_scan: str = "off",
    mesh_shape: tp.Optional[tp.Mapping[str, int]] = None,
    traffic: bool = False,
    temperature: float = 0.0,
    top_k: tp.Optional[int] = None,
):
    """One-call audit of the speculative verify program: donation-intact,
    no-host-sync, no-f64 (+ no-dequant-materialization when ``quant``)
    — the CI serving-audit job runs this next to
    :func:`audit_decode_window` and :func:`audit_prefill_chunk` so all
    three serving hot-path programs are gated on one geometry.
    ``temperature > 0`` audits the rejection-sampling verify program
    against the SAME budgets: sampled acceptance must not cost a launch,
    a host sync, or a traffic band."""
    cfg = (
        get_config(name_or_cfg)
        if isinstance(name_or_cfg, str)
        else name_or_cfg
    )
    hlo, mesh, donated, block, wshapes, payload, keys = (
        compile_verify_program(
            cfg, slots=slots, spec_len=spec_len, page_size=page_size,
            shrink=shrink, quant=quant, kv_quant=kv_quant,
            layer_scan=layer_scan, mesh_shape=mesh_shape,
            temperature=temperature, top_k=top_k,
        )
    )
    analysis = StepAnalysis.from_text(
        hlo,
        hlo_mod.MeshInfo.from_mesh(mesh, num_slices=1),
        global_batch=slots,
        block=block,
        donated_leaves=donated,
    )
    report = _serving_rules(wshapes, payload, slots).evaluate(analysis)
    if traffic:
        return analysis, report, _serving_traffic(
            "verify_program", analysis, keys, window_steps=1
        )
    return analysis, report


def prove_serving_choreography(
    name_or_cfg: tp.Union[str, ExperimentConfig],
    *,
    slots: int = 4,
    window: int = 2,
    spec_len: int = 2,
    chunk_len: int = 16,
    page_size: int = 16,
    quant: bool = False,
    kv_quant: bool = False,
    paged_kernel: str = "xla",
    temperature: float = 0.0,
    top_k: tp.Optional[int] = None,
):
    """Run the arithmetic-choreography prover
    (:mod:`midgpt_tpu.analysis.choreo`) over the three serving programs
    of ``cfg``'s model family: trace each program to a jaxpr (through
    the very jitted callables the engine launches), slice out the
    attention and lm-head subgraphs, normalize them into op-and-dtype
    traces, and prove the three contracts — verify mirrors decode op
    for op (PR 5), the prefill chunk's softmax core mirrors
    ``naive_attention`` (PR 4), and the shared arithmetic (f32 softmax
    and accumulation, mask-before-scale, one lm-head choreography)
    holds everywhere. Returns a :class:`~midgpt_tpu.analysis.choreo.\
ChoreoReport`.

    Traced at choreography size (2 layers, block 64, vocab 128): the
    contract is per-layer-identical by construction (asserted by the
    extractor), so depth and width add nothing but trace time. No
    compilation happens — a full proof is seconds on CPU. ``quant``
    proves the int8 WEIGHT path instead (same contracts; the lm-head
    check additionally pins the dequant epilogue everywhere).
    ``kv_quant`` traces the programs against an int8 KV pool and
    additionally proves every program carries the pool's
    codes-times-scale dequant. ``paged_kernel="pallas"`` traces the
    Pallas ragged-walk programs: the kernel appears as one contract
    node in the attention traces and its BODY's softmax signature is
    what the decode/verify checks then compare — a bf16-accumulating
    kernel variant fails exactly like a bf16-accumulating XLA edit.
    ``temperature > 0`` traces the SAMPLED programs instead and appends
    the four sampled-verify checks
    (:func:`~midgpt_tpu.analysis.choreo.prove_sampled_choreography`):
    the verify row-0 categorical mirrors the decode window's sampler op
    for op, the rejection-sampling acceptance compare runs in f32, and
    the residual renormalization + target softmax run in f32."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from midgpt_tpu.analysis.choreo import (
        ChoreoReport,
        extract_choreography,
        extract_sampler_choreography,
        prove_choreography,
        prove_sampled_choreography,
    )
    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.ops.attention import naive_attention
    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.serving.engine import trace_serving_programs

    cfg = (
        get_config(name_or_cfg)
        if isinstance(name_or_cfg, str)
        else name_or_cfg
    )
    model_cfg = _dc.replace(
        cfg.model, n_layer=2, block_size=64, vocab_size=128,
        remat="none", scan_unroll=1,
    )
    model = cast_floating(
        GPT.init(jax.random.PRNGKey(0), model_cfg), jnp.bfloat16
    )
    if quant:
        from midgpt_tpu.quant import quantize_model

        model = quantize_model(model)
    jaxprs = trace_serving_programs(
        model, slots=slots, window=window, spec_len=spec_len,
        chunk_len=chunk_len, page_size=page_size,
        kv_quant="int8" if kv_quant else None, paged_kernel=paged_kernel,
        temperature=temperature, top_k=top_k,
    )

    # the naive reference: what the monolithic prefill / training
    # forward computes (ops.attention docstring: the correctness
    # oracle). q/k/v are derived from the traced input by an identity
    # multiply so the score contraction's operands are computed values,
    # not entry parameters (the prover classifies parameter-operand
    # contractions as weight projections).
    h, hkv, c = model_cfg.n_head, model_cfg.kv_heads, model_cfg.head_dim
    t = 8

    def naive_ref(x):
        one = jnp.asarray(1.0, x.dtype)
        q = x[:, :h] * one
        k = x[:, h : h + hkv] * one
        v = x[:, h + hkv :] * one
        return naive_attention(q, k, v, causal=True)

    naive_jaxpr = jax.make_jaxpr(naive_ref)(
        jax.ShapeDtypeStruct((1, h + 2 * hkv, t, c), jnp.bfloat16)
    )
    report = prove_choreography(
        decode=extract_choreography("decode_window", jaxprs["decode_window"]),
        prefill=extract_choreography("prefill_chunk", jaxprs["prefill_chunk"]),
        verify=extract_choreography("verify", jaxprs["verify"]),
        naive=extract_choreography("naive_reference", naive_jaxpr),
        expect_kv_dequant=kv_quant,
    )
    if temperature > 0.0:
        sampled = prove_sampled_choreography(
            extract_sampler_choreography(
                "decode_window", jaxprs["decode_window"]
            ),
            extract_sampler_choreography("verify", jaxprs["verify"]),
        )
        report = ChoreoReport(
            checks=report.checks + sampled, programs=report.programs
        )
    return report


def prove_block_choreography(
    model_cfg,
    *,
    slots: int = 4,
    window: int = 2,
    chunk_len: int = 16,
    page_size: int = 16,
):
    """The choreography suite for a BLOCK-DIFFUSION model
    (``model_cfg.block_len`` > 0), whose engine has no token-at-a-time
    decode window to hold the verify program to. The verify-mirrors-decode
    clause is kept with the roles moved up one: the block window's forward
    (serving.engine._build_block_window: two blocks of rows a slot, the
    one that lands and the one being denoised) must mirror the VERIFY
    program's of the same model at T = 2 block_len OP FOR OP — it is the
    verify forward under another mask, and the mask is an operand, so the two
    attention traces and softmax signatures are equal or something other
    than the mask changed. The prefill chunk is still held to
    ``naive_attention``, and the shared clauses (f32 softmax and
    accumulation, mask before scale, one lm-head choreography, banded
    order) to all three. Proved of the gather path: the Pallas kernel
    gives the block forward a contraction of its own (the matrix unit:
    ops.paged_attn, THE BLOCK FORWARD'S CONTRACTION), which is held to
    this path at a tolerance by tests, not op for op by a prover. Traced
    at choreography size (2 layers, block 64, vocab 128), no
    compilation."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from midgpt_tpu.analysis.choreo import (
        ChoreoCheck,
        ChoreoReport,
        extract_choreography,
        prove_choreography,
    )
    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.ops.attention import naive_attention
    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.serving.engine import (
        make_block_window,
        make_prefill_chunk_program,
        make_verify_program,
    )
    from midgpt_tpu.serving.paged import PagedKVPool, pages_needed

    assert model_cfg.block_len > 0, "not a block-diffusion model"
    blk = model_cfg.block_len
    cfg = _dc.replace(
        model_cfg, n_layer=2, block_size=64, vocab_size=128,
        mask_token=127, remat="none", scan_unroll=1,
    )
    model = cast_floating(GPT.init(jax.random.PRNGKey(0), cfg), jnp.bfloat16)
    pmax = pages_needed(cfg.block_size, page_size)
    pool = jax.eval_shape(
        lambda: PagedKVPool.init(cfg, slots * pmax, page_size)
    )
    sds = jax.ShapeDtypeStruct
    i32 = lambda *s: sds(s, jnp.int32)  # noqa: E731
    pred = lambda *s: sds(s, jnp.bool_)  # noqa: E731
    logits = sds((slots, cfg.vocab_size), jnp.float32)
    geometry = dict(pmax=pmax, rope_len=cfg.block_size)
    block_jaxpr = jax.make_jaxpr(make_block_window(
        model, slots=slots, window=window, paged_kernel="xla", **geometry,
    ))(
        model, pool, i32(slots, pmax), i32(slots), pred(slots), i32(slots),
        i32(slots), i32(slots), i32(slots, blk), pred(slots, blk),
        i32(slots, blk), pred(slots), i32(slots, blk),
    )
    verify_jaxpr = jax.make_jaxpr(make_verify_program(
        model, slots=slots, spec_len=2 * blk - 1, paged_kernel="xla",
        **geometry,
    ))(
        model, pool, logits, i32(slots, pmax), i32(slots), pred(slots),
        i32(slots), i32(slots), i32(slots), i32(slots, 2 * blk - 1),
        i32(slots),
    )
    chunk_jaxpr = jax.make_jaxpr(make_prefill_chunk_program(
        model, chunk_len=chunk_len, **geometry,
    ))(
        model, pool, logits, i32(), i32(1, chunk_len), i32(), i32(),
        i32(pmax),
    )
    h, hkv, c = cfg.n_head, cfg.kv_heads, cfg.head_dim

    def naive_ref(x):
        one = jnp.asarray(1.0, x.dtype)
        return naive_attention(
            x[:, :h] * one, x[:, h : h + hkv] * one, x[:, h + hkv :] * one,
            causal=True,
        )

    naive_jaxpr = jax.make_jaxpr(naive_ref)(
        sds((1, h + 2 * hkv, 8, c), jnp.bfloat16)
    )
    report = prove_choreography(
        decode=extract_choreography("verify", verify_jaxpr),
        prefill=extract_choreography("prefill_chunk", chunk_jaxpr),
        verify=extract_choreography("block_window", block_jaxpr),
        naive=extract_choreography("naive_reference", naive_jaxpr),
    )
    # a block-diffusion prompt's chunk leaves K/V and projects nothing (its
    # first block opens from the mask token): the lm-head clause is the
    # two programs' that have a head
    heads = {
        p.name: (p.lm_head, p.lm_head_epilogue) for p in report.programs
        if p.name in ("verify", "block_window")
    }
    renamed = {
        "verify-mirrors-decode": lambda c_: ChoreoCheck(
            "block-window-mirrors-verify", c_.ok, c_.detail
        ),
        "shared: lm-head projection choreography is identical "
        "everywhere": lambda c_: ChoreoCheck(
            c_.name, len(set(heads.values())) == 1, str(heads)
        ),
    }
    checks = tuple(
        renamed.get(c_.name, lambda x: x)(c_) for c_ in report.checks
    )
    assert len({c_.name for c_ in checks} & {
        "block-window-mirrors-verify",
        "shared: lm-head projection choreography is identical everywhere",
    }) == 2, [c_.name for c_ in checks]
    return ChoreoReport(checks=checks, programs=report.programs)


def prove_sp_prefill_choreography(
    name_or_cfg: tp.Union[str, ExperimentConfig],
    *,
    quant: bool = False,
    kv_quant: bool = False,
    layer_scan: str = "off",
    tp_size: int = 2,
    chunk_len: int = 16,
    page_size: int = 16,
):
    """The sequence-parallel prefill leg of the choreography suite:
    trace the prefill-chunk program TWICE on one ``tensor=tp_size`` mesh
    — ``prefill_sp`` off and on, through the very jitted factory the
    engine launches — and prove the two normalized traces identical op
    for op (:func:`~midgpt_tpu.analysis.choreo.prove_sp_choreography`).
    SP row-shards the chunk's replicated segments over 'tensor' with
    ``sharding_constraint`` ops only; any arithmetic difference between
    the traces is a bitwise-identity hazard (the landing gate for
    ``ServingEngine(prefill_sp=...)``). Tracing only — no compilation;
    needs ``tp_size`` visible devices for the mesh the constraints
    name."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from midgpt_tpu.analysis.choreo import (
        extract_choreography,
        prove_sp_choreography,
    )
    from midgpt_tpu.config import MeshConfig
    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.parallel.mesh import create_mesh
    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.serving.engine import trace_serving_programs

    cfg = (
        get_config(name_or_cfg)
        if isinstance(name_or_cfg, str)
        else name_or_cfg
    )
    model_cfg = _dc.replace(
        cfg.model, n_layer=2, block_size=64, vocab_size=128,
        remat="none", scan_unroll=1,
    )
    assert model_cfg.kv_heads % tp_size == 0, (
        f"tensor={tp_size} must divide kv_heads {model_cfg.kv_heads}"
    )
    model = cast_floating(
        GPT.init(jax.random.PRNGKey(0), model_cfg), jnp.bfloat16
    )
    if quant:
        from midgpt_tpu.quant import quantize_model

        model = quantize_model(model)
    mesh = create_mesh(
        MeshConfig(replica=1, fsdp=1, sequence=1, tensor=tp_size),
        devices=jax.devices()[:tp_size],
    )
    kw = dict(
        slots=4, window=2, spec_len=2, chunk_len=chunk_len,
        page_size=page_size, kv_quant="int8" if kv_quant else None,
        layer_scan=layer_scan, mesh=mesh,
    )
    off = trace_serving_programs(model, prefill_sp="off", **kw)
    on = trace_serving_programs(model, prefill_sp="on", **kw)
    return prove_sp_choreography(
        extract_choreography("prefill_chunk", off["prefill_chunk"]),
        extract_choreography("prefill_chunk_sp", on["prefill_chunk"]),
    )


def prove_scan_equivalence(
    name_or_cfg: tp.Union[str, ExperimentConfig],
    *,
    quant: bool = False,
    kv_quant: bool = False,
    paged_kernel: str = "xla",
    n_layer: int = 3,
):
    """Run the scan-equivalence prover (:mod:`midgpt_tpu.analysis.fusion`)
    over the three serving programs of ``cfg``'s model family: trace
    each program BOTH ways (``layer_scan`` off and on, through the very
    jitted factories the engine launches), prove the unrolled traces
    layer-homogeneous (the fold's legality precondition), and prove the
    fused scan BODY's normalized trace op-for-op equal to the unrolled
    per-layer trace — attention region, full layer segment, softmax
    signature, lm-head choreography. Returns a
    :class:`~midgpt_tpu.analysis.fusion.FusionReport`.

    Traced at depth 3 (not the choreography size's 2): homogeneity
    needs a TRUE MIDDLE layer — at depth 2 every layer is first or
    last, and a first/last-layer special case would have nothing
    identical to be compared against. No compilation; a full proof of
    all six traces is seconds on CPU."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from midgpt_tpu.analysis.fusion import prove_scan_fusion
    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.serving.engine import trace_serving_programs

    cfg = (
        get_config(name_or_cfg)
        if isinstance(name_or_cfg, str)
        else name_or_cfg
    )
    model_cfg = _dc.replace(
        cfg.model, n_layer=n_layer, block_size=64, vocab_size=128,
        remat="none", scan_unroll=1,
    )
    model = cast_floating(
        GPT.init(jax.random.PRNGKey(0), model_cfg), jnp.bfloat16
    )
    if quant:
        from midgpt_tpu.quant import quantize_model

        model = quantize_model(model)
    kw = dict(
        slots=4, window=2, spec_len=2, chunk_len=16, page_size=16,
        kv_quant="int8" if kv_quant else None, paged_kernel=paged_kernel,
    )
    off = trace_serving_programs(model, layer_scan="off", **kw)
    on = trace_serving_programs(model, layer_scan="on", **kw)
    return prove_scan_fusion(off, on)


def serving_dispatch_reports(
    name_or_cfg: tp.Union[str, ExperimentConfig],
    *,
    layer_scan: str = "off",
    prefill_sp: str = "off",
    quant: bool = False,
    kv_quant: bool = False,
    paged_kernel: str = "xla",
    slots: int = 4,
    window: int = 4,
    spec_len: int = 4,
    chunk_len: int = 64,
    page_size: int = 16,
    temperature: float = 0.0,
    top_k: tp.Optional[int] = None,
) -> tp.Dict[str, tp.Any]:
    """Trace the three serving programs at the audit geometry (the same
    n_layer=2 shrink the byte budgets were measured at) and build their
    static :class:`~midgpt_tpu.analysis.dispatch.DispatchReport`\\ s,
    keyed by the budget program names (``decode_window`` /
    ``prefill_chunk`` / ``verify_program``). Launch structure is
    precision-independent (quant/kv-quant change dtypes, not the scan
    nesting) — the flags exist so fault-injection tests can audit any
    cell they traced. ``temperature > 0`` audits the SAMPLED programs
    against the same cells: rejection-sampling acceptance is in-program
    arithmetic and must not change the launch structure.
    ``prefill_sp="on"`` additionally traces the sequence-parallel chunk
    program on a tensor=2 mesh and reports it as ``prefill_chunk_sp``:
    SP is resharding only, so its launch structure must equal the plain
    chunk's (its own DISPATCH_BUDGETS cells pin exactly that)."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from midgpt_tpu.analysis.dispatch import dispatch_report
    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.pytree import cast_floating
    from midgpt_tpu.serving.engine import trace_serving_programs

    cfg = (
        get_config(name_or_cfg)
        if isinstance(name_or_cfg, str)
        else name_or_cfg
    )
    model_cfg = _dc.replace(
        cfg.model, n_layer=2, block_size=256, vocab_size=1024,
        remat="none", scan_unroll=1,
    )
    model = cast_floating(
        GPT.init(jax.random.PRNGKey(0), model_cfg), jnp.bfloat16
    )
    if quant:
        from midgpt_tpu.quant import quantize_model

        model = quantize_model(model)
    jaxprs = trace_serving_programs(
        model, slots=slots, window=window, spec_len=spec_len,
        chunk_len=chunk_len, page_size=page_size,
        kv_quant="int8" if kv_quant else None,
        paged_kernel=paged_kernel, layer_scan=layer_scan,
        temperature=temperature, top_k=top_k,
    )
    out = {
        "decode_window": dispatch_report(
            jaxprs["decode_window"], program="decode_window",
            window_steps=window,
        ),
        "prefill_chunk": dispatch_report(
            jaxprs["prefill_chunk"], program="prefill_chunk",
        ),
        "verify_program": dispatch_report(
            jaxprs["verify"], program="verify_program",
        ),
    }
    if prefill_sp == "on":
        from midgpt_tpu.config import MeshConfig
        from midgpt_tpu.parallel.mesh import create_mesh

        assert model_cfg.kv_heads % 2 == 0, model_cfg.kv_heads
        mesh = create_mesh(
            MeshConfig(replica=1, fsdp=1, sequence=1, tensor=2),
            devices=jax.devices()[:2],
        )
        sp_jaxprs = trace_serving_programs(
            model, slots=slots, window=window, spec_len=spec_len,
            chunk_len=chunk_len, page_size=page_size,
            kv_quant="int8" if kv_quant else None,
            paged_kernel=paged_kernel, layer_scan=layer_scan,
            prefill_sp="on", mesh=mesh,
            temperature=temperature, top_k=top_k,
        )
        out["prefill_chunk_sp"] = dispatch_report(
            sp_jaxprs["prefill_chunk"], program="prefill_chunk_sp",
        )
    return out


def audit_serving_dispatch(
    name_or_cfg: tp.Union[str, ExperimentConfig],
    *,
    layer_scan: str = "off",
    **kw,
) -> tp.Tuple[tp.Dict[str, tp.Any], tp.List[str]]:
    """One-call dispatch audit: trace the three programs with the given
    ``layer_scan`` and gate their launch structure against the
    checked-in :data:`~midgpt_tpu.analysis.budgets.DISPATCH_BUDGETS`
    cells for that value. Returns ``(reports, violations)`` — the CI
    serving-choreo job runs this for BOTH values, so a re-unrolled
    fused program (zero byte movement, L× launch structure) fails the
    "on" cells before any hardware sees it."""
    from midgpt_tpu.analysis.budgets import (
        check_dispatch_budget,
        dispatch_budget_for,
    )

    reports = serving_dispatch_reports(
        name_or_cfg, layer_scan=layer_scan, **kw
    )
    violations: tp.List[str] = []
    for name, rep in reports.items():
        budget = dispatch_budget_for(name, layer_scan)
        if budget is not None:
            violations.extend(check_dispatch_budget(rep, budget))
    return reports, violations


def train_step_comms_summary(
    cfg: ExperimentConfig, *, window_steps: tp.Optional[int] = None
) -> tp.Dict[str, tp.Any]:
    """Flat scalar comms summary for an already-benchmarked config —
    bench.py attaches this to its one-JSON-line record. Compiles the
    program the bench actually dispatched: the fused K-step window when
    ``window_steps > 1`` (bench's scan dispatch mode), the single step
    otherwise (the executable cache makes either a cache hit right
    after the bench rung compiled the same program). Per-axis byte
    splits are flattened into ``comms_axis_<axis>_bytes_per_step``
    scalars ('+' -> '_') so the one-line JSON record stays flat."""
    from midgpt_tpu.analysis.cost import cost_report

    k = window_steps if window_steps is not None else 1
    if k > 1:
        hlo, mesh, donated, _ = compile_train_window(cfg, k)
        analysis = StepAnalysis.from_text(
            hlo,
            hlo_mod.MeshInfo.from_mesh(
                mesh, num_slices=cfg.mesh.num_slices
            ),
            global_batch=cfg.batch_size,
            block=cfg.model.block_size,
            donated_leaves=donated,
        )
    else:
        analysis = analyze_train_step(cfg, shrink=False)
    rep = cost_report(analysis)
    out: tp.Dict[str, tp.Any] = {
        "comms_traffic_bytes_per_step": rep["value"],
        "comms_dcn_bytes_per_step": rep["dcn_bytes"],
        "comms_ici_bytes_per_step": rep["ici_bytes"],
        "comms_collective_count": rep["collective_count"],
        "comms_window_steps": k,
    }
    for axis, b in sorted(dict(rep["by_axis"]).items()):
        out[f"comms_axis_{axis.replace('+', '_')}_bytes_per_step"] = b
    return out


# ---------------------------------------------------------------------------
# TRAIN-side verification suite (analysis --train-audit): precision
# choreography prover + traffic cells + window dispatch gate for the
# fused K-step train window, at the checked-in audit geometry matrix.
# ---------------------------------------------------------------------------


def shrink_for_train_audit(
    cfg: ExperimentConfig,
    geometry: str,
    *,
    remat: str = "none",
) -> ExperimentConfig:
    """Audit-sized variant of ``cfg`` pinned to the train budget cell
    geometry (:data:`~midgpt_tpu.analysis.budgets.TRAIN_AUDIT_GEOMETRY`
    × :data:`~midgpt_tpu.analysis.budgets.TRAIN_AUDIT_GEOMETRIES`):
    the real trainer's code paths (grad accumulation G=2, fused window,
    layer scan) shrunk so every mesh geometry in the matrix compiles in
    seconds on the 8-device CPU virtual mesh. ``batch_size`` 16 keeps
    the microbatch divisible by every batch sharding in the matrix
    (8-way fsdp, 2×4 replica×fsdp, 4-way fsdp under tensor=2)."""
    from midgpt_tpu.analysis.budgets import (
        TRAIN_AUDIT_GEOMETRIES,
        TRAIN_AUDIT_GEOMETRY,
    )
    from midgpt_tpu.config import MeshConfig

    g = TRAIN_AUDIT_GEOMETRY
    model = dataclasses.replace(
        cfg.model,
        n_layer=g["n_layer"],
        block_size=g["block_size"],
        vocab_size=g["vocab_size"],
        remat=remat,
        scan_unroll=1,
    )
    return dataclasses.replace(
        cfg,
        model=model,
        batch_size=g["batch_size"],
        g_accum_iters=g["g_accum_iters"],
        loss_chunk=None,
        mesh=MeshConfig(**TRAIN_AUDIT_GEOMETRIES[geometry]),
    )


def compile_train_window(
    cfg: ExperimentConfig,
    window_steps: int,
    *,
    tx=None,
    param_rules=None,
    logical_overrides: tp.Optional[tp.Mapping[str, tp.Any]] = None,
):
    """Compile the fused K-step window UNCONDITIONALLY — unlike
    :func:`compile_train_step`, which picks the per-step jit at
    ``steps_per_dispatch == 1``. The train budget cells gate the window
    program at both K=1 and K=4, and the byte identity between them is
    itself a checked invariant (a window whose bytes grow with K has
    lost the scan). ``tx`` / ``param_rules`` / ``logical_overrides``
    are fault-injection seams (a mis-dtyped optimizer chain, a widened
    sharding spec); production callers leave them None.

    Returns ``(hlo_text, mesh, donated_leaves, aliased_leaves)`` —
    the last is the count of distinct entry parameters the compiled
    executable input/output-aliases (the donation accounting)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from midgpt_tpu.parallel.mesh import create_mesh
    from midgpt_tpu.parallel.sharding import make_global_array
    from midgpt_tpu.train import init_state, make_optimizer, make_train_window

    mesh = create_mesh(cfg.mesh)
    if tx is None:
        tx, _ = make_optimizer(cfg)
    rules_kw = {} if param_rules is None else {"param_rules": param_rules}
    with override_logical_rules(logical_overrides):
        state = init_state(
            cfg, mesh, tx, jax.random.PRNGKey(0), abstract=True, **rules_kw
        )
        step = make_train_window(cfg, tx, mesh, window_steps, **rules_kw)
        x = np.zeros(
            (window_steps, cfg.g_accum_iters, cfg.microbatch_size,
             cfg.model.block_size),
            np.int32,
        )
        xg = make_global_array(x, mesh, P(None, *BATCH_SPEC_AXES))
        hlo = step.lower(
            state, xg, xg, jax.random.PRNGKey(1)
        ).compile().as_text()
    donated = len(jax.tree.leaves(state))
    aliased = len({
        e.param_number for e in hlo_mod.parse_input_output_alias(hlo)
    })
    return hlo, mesh, donated, aliased


def trace_train_window(
    cfg: ExperimentConfig,
    window_steps: int,
    *,
    mesh=None,
    tx=None,
    use_cache: bool = True,
):
    """Trace (``jax.make_jaxpr``) + ``jax.eval_shape`` the fused window
    program. ``use_cache=True`` resolves it through
    ``train.get_train_window`` — the very cache the trainer launches
    from, so the proof covers the shipped lookup path, not a
    reconstruction. Fault-injection callers pass ``use_cache=False``
    (plus ``tx``) to build a poisoned window via ``make_train_window``
    without polluting the shared cache. Returns
    ``(closed_jaxpr, (new_state, aux) shape tree)``."""
    import jax
    import jax.numpy as jnp

    from midgpt_tpu.parallel.mesh import create_mesh
    from midgpt_tpu.train import (
        get_train_window,
        init_state,
        make_optimizer,
        make_train_window,
    )

    if mesh is None:
        mesh = create_mesh(cfg.mesh)
    if tx is None:
        tx, _ = make_optimizer(cfg)
    state = init_state(cfg, mesh, tx, jax.random.PRNGKey(0), abstract=True)
    xs = jax.ShapeDtypeStruct(
        (window_steps, cfg.g_accum_iters, cfg.microbatch_size,
         cfg.model.block_size),
        jnp.int32,
    )
    if use_cache:
        prog = get_train_window(cfg, mesh, window_steps)
    else:
        prog = make_train_window(cfg, tx, mesh, window_steps)
    key = jax.random.PRNGKey(1)
    closed = jax.make_jaxpr(prog)(state, xs, xs, key)
    out_tree = jax.eval_shape(prog, state, xs, xs, key)
    return closed, out_tree


def prove_train_window_choreography(
    cfg: ExperimentConfig,
    geometry: str,
    window_steps: int,
):
    """Run the mixed-precision choreography prover on the REAL cached
    window program at the audit geometry: traces the ``remat="none"``
    leg through ``train.get_train_window`` plus a ``remat="full"`` leg
    for the recompute-structure check. Returns the
    :class:`~midgpt_tpu.analysis.train_choreo.TrainChoreoReport`."""
    from midgpt_tpu.analysis.train_choreo import prove_window_choreography

    base = shrink_for_train_audit(cfg, geometry, remat="none")
    closed, out_tree = trace_train_window(base, window_steps)
    remat_cfg = shrink_for_train_audit(cfg, geometry, remat="full")
    remat_closed, _ = trace_train_window(remat_cfg, window_steps)
    return prove_window_choreography(
        closed,
        out_tree,
        window_steps=window_steps,
        g_accum_iters=base.g_accum_iters,
        remat_closed=remat_closed,
    )


def train_traffic_cell(
    cfg: ExperimentConfig, geometry: str, window_steps: int
) -> tp.Dict[str, tp.Any]:
    """Compile the window at the audit geometry and measure its budget
    cell: ICI/DCN collective wire bytes + the per-mesh-axis split
    (cost.py's ring arithmetic on the compiled HLO), plus the donation
    accounting off the same executable. Keys line up with
    :data:`~midgpt_tpu.analysis.budgets.TRAIN_BUDGETS`."""
    from midgpt_tpu.analysis.cost import cost_report

    audit = shrink_for_train_audit(cfg, geometry)
    hlo, mesh, donated, aliased = compile_train_window(audit, window_steps)
    analysis = StepAnalysis.from_text(
        hlo,
        hlo_mod.MeshInfo.from_mesh(mesh, num_slices=audit.mesh.num_slices),
        global_batch=audit.batch_size,
        block=audit.model.block_size,
        donated_leaves=donated,
    )
    rep = cost_report(analysis)
    return {
        "geometry": geometry,
        "window_steps": window_steps,
        "ici_bytes": rep["ici_bytes"],
        "dcn_bytes": rep["dcn_bytes"],
        "collective_count": rep["collective_count"],
        "by_axis": dict(rep["by_axis"]),
        "donated_leaves": donated,
        "aliased_leaves": aliased,
    }


def train_dispatch_cell(
    cfg: ExperimentConfig, geometry: str, window_steps: int
):
    """Trace-level window dispatch report at the audit geometry (the
    launch-structure half of the train gate; the donation half rides
    the compiled :func:`train_traffic_cell`)."""
    from midgpt_tpu.analysis.dispatch import train_dispatch_report

    audit = shrink_for_train_audit(cfg, geometry)
    closed, _ = trace_train_window(audit, window_steps)
    return train_dispatch_report(
        closed,
        window_steps=window_steps,
        g_accum_iters=audit.g_accum_iters,
    )


def audit_train(
    name_or_cfg: tp.Union[str, ExperimentConfig],
    geometry: str,
    window_steps: tp.Sequence[int] = (1, 4),
) -> tp.Dict[str, tp.Any]:
    """One-call train audit for one mesh geometry: for each K, prove
    the precision choreography on the cached window trace, gate the
    compiled wire bytes against
    :data:`~midgpt_tpu.analysis.budgets.TRAIN_BUDGETS`, and gate the
    launch structure + donation against
    :data:`~midgpt_tpu.analysis.budgets.TRAIN_DISPATCH_BUDGETS`.
    Returns a JSON-able report with a flat ``violations`` list
    (empty = green)."""
    from midgpt_tpu.analysis.budgets import (
        check_train_budget,
        check_train_dispatch_budget,
        train_budget_for,
    )

    cfg = (
        get_config(name_or_cfg)
        if isinstance(name_or_cfg, str)
        else name_or_cfg
    )
    cells: tp.List[tp.Dict[str, tp.Any]] = []
    violations: tp.List[str] = []
    for k in window_steps:
        prover = prove_train_window_choreography(cfg, geometry, k)
        for c in prover.checks:
            if not c.ok:
                violations.append(
                    f"train_window[{geometry}] k={k}: prover check "
                    f"'{c.name}' failed — {c.detail}"
                )
        traffic = train_traffic_cell(cfg, geometry, k)
        budget = train_budget_for(geometry, k)
        if budget is None:
            violations.append(
                f"train_window[{geometry}] k={k}: no checked-in budget "
                "cell — regenerate with --print-budgets"
            )
        else:
            violations.extend(
                f"k={k}: {v}"
                for v in check_train_budget(traffic, budget,
                                            geometry=geometry)
            )
        disp = train_dispatch_cell(cfg, geometry, k)
        violations.extend(
            f"k={k}: {v}"
            for v in check_train_dispatch_budget(
                disp, aliased_leaves=traffic["aliased_leaves"]
            )
        )
        cells.append({
            "window_steps": k,
            "choreography": prover.to_dict(),
            "traffic": traffic,
            "dispatch": disp.to_dict(),
        })
    return {
        "geometry": geometry,
        "ok": not violations,
        "violations": violations,
        "cells": cells,
    }


def prove_telemetry_inert(
    *,
    slots: int = 2,
    window: int = 4,
    page_size: int = 8,
    prefill_chunk: tp.Optional[int] = 4,
    speculate: int = 0,
    max_new: int = 8,
) -> tp.Dict[str, tp.Any]:
    """Prove the serving telemetry layer cannot perturb the dispatch
    pipeline (the ``--telemetry on`` audit leg).

    Telemetry is deliberately NOT a parameter of any serving program
    factory, so the proof is two identities on a pair of engines that
    differ only in ``telemetry=``:

    1. **Program identity** — both engines must resolve to the *same*
       cached jitted callables (``is``, not ``==``). Every audit result
       established for the untraced programs — donation 3/3,
       no-host-sync, traffic + dispatch budgets — then applies verbatim
       to the traced engine, because it launches the very same
       executables.
    2. **Stream identity** — greedy token streams bitwise equal with
       tracing on vs off, and the traced run actually recorded events
       (a vacuously-inert telemetry that never fired would pass 1 for
       the wrong reason).

    The identities are engine-logic properties, independent of model
    size, so the proof runs on a fixed tiny model in seconds — like the
    choreography prover, no compilation of the named config is needed.
    Raises ``AssertionError`` on violation; returns a report dict.
    """
    import jax
    import jax.numpy as jnp

    from midgpt_tpu.config import ModelConfig
    from midgpt_tpu.models.gpt import GPT
    from midgpt_tpu.serving import ServingEngine

    cfg = ModelConfig(
        block_size=64, vocab_size=96, n_layer=2, n_head=4, n_embd=32,
        dropout=0.0, attn_impl="naive", remat="none",
    )
    model = GPT.init(jax.random.PRNGKey(0), cfg)
    prompts = [
        np.asarray(
            jax.random.randint(
                jax.random.PRNGKey(7 + i), (5 + 3 * i,), 0, cfg.vocab_size
            )
        )
        for i in range(3)
    ]
    kw = dict(
        slots=slots, window=window, page_size=page_size,
        prefill_chunk=prefill_chunk, speculate=speculate,
        temperature=0.0, cache_dtype=jnp.float32,
    )

    def drive(telemetry):
        eng = ServingEngine(model, telemetry=telemetry, **kw)
        rids = [eng.submit(p, max_new, seed=i) for i, p in enumerate(prompts)]
        fin = eng.run()
        return eng, [list(map(int, fin[r].tokens)) for r in rids]

    eng_off, streams_off = drive(None)
    eng_on, streams_on = drive(True)
    checked = []
    for attr in ("_window_fn", "_verify_fn"):
        off_fn, on_fn = getattr(eng_off, attr), getattr(eng_on, attr)
        assert off_fn is on_fn, (
            f"{attr}: tracing selected a different program object — "
            "telemetry leaked into the program cache key"
        )
        if off_fn is not None:
            checked.append(attr)
    for bucket, fn in eng_off._chunk_fns.items():
        assert eng_on._chunk_fns.get(bucket) is fn, (
            f"prefill bucket {bucket}: tracing selected a different "
            "program object"
        )
        checked.append(f"_chunk_fns[{bucket}]")
    assert streams_on == streams_off, (
        "greedy streams diverged with tracing on — telemetry perturbed "
        "the dispatch pipeline"
    )
    n_events = len(eng_on.telemetry.events)
    assert n_events > 0, "traced run recorded no events (vacuous pass)"
    return {
        "ok": True,
        "programs_identical": checked,
        "streams_identical": True,
        "requests": len(prompts),
        "events_recorded": n_events,
        "dispatch_records": len(eng_on.telemetry.dispatches),
    }
