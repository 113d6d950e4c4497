"""HLO contract gate (rules H001-H004): lower every serving dispatch
on a forced 8-device CPU mesh and assert the compiled modules keep the
stack's load-bearing promises.

The serving invariants — in-place KV updates, a host-callback-free
decode tick, GSPMD-sharded bank params, a bounded executable ladder —
are all *silent* to Python: XLA drops an unusable donation with only a
warning, a stray ``jax.debug`` or shape-dependent reshape lowers
happily, and a sharding regression just makes everything slower. This
pass reads the compiled HLO instead of trusting the call sites:

  H001  buffer donation took: every donated argument (the prefill/
        decode KV pool planes, the COW copy pool, the hub install's
        slot stack) appears in the module's ``input_output_alias`` map
        — no alias entry means XLA is double-buffering the engine's
        largest array every dispatch.
  H002  the decode tick is device-pure: no ``custom-call`` host
        callbacks (``xla_python_cpu_callback`` et al.), no infeed/
        outfeed, no ``dynamic-reshape``/``dynamic-pad`` (shape-dynamic
        ops that force a host round-trip or defeat bucketing).
  H003  sharding annotations on the bank params match the placement
        spec: every param leaf of a mesh-built engine's dispatch is
        ``PartitionSpec('expert', ...)`` on the leading axis.
  H004  executable count equals the declared bucket bound after a full
        warmup — ``EngineCore.executable_bounds()``, the one source of
        the ladder arithmetic: monolithic prefills for buckets up to
        ``chunk_len``, one suffix executable per (batch bucket, chunk
        index) pair, ``len(batch_buckets)`` decode steps, one hub
        install, and — on speculating engines — one verify executable
        per batch bucket (``k`` is fixed per engine). The paged hub
        here is built *chunked* so the gate exercises the chunk-ladder
        bound the serving bench asserts; a dedicated spec engine
        drives a wrap-risk admission grid so BOTH the verify family
        and its gate-blocked decode fallback are proven exactly full —
        the zero-steady-state-recompile contract, checked exactly and
        in seconds rather than minutes.

Requires >= 8 devices (``XLA_FLAGS=--xla_force_host_platform_device_count=8``
set before jax initialises — the ``python -m repro.analysis`` CLI
re-execs itself into such an environment automatically; pytest callers
use a subprocess, see ``tests/test_analysis.py``).
"""
from __future__ import annotations

import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import Violation

_CALLBACK_MARKERS = ("callback", "infeed", "outfeed", "send", "recv")
_DYNAMIC_OPS = ("dynamic-reshape", "dynamic-pad")

_HUB_PATH = "src/repro/serve/hub.py"
_CORE_PATH = "src/repro/serve/core.py"


def _require_devices(n: int = 8) -> None:
    import jax
    have = len(jax.devices())
    if have < n:
        raise EnvironmentError(
            f"hlo contract pass needs {n} devices, found {have}; run "
            "via `python -m repro.analysis hlo` (which re-execs with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8) or "
            "set the flag before jax initialises")


def _flat_arg_offsets(args: Sequence[Any]) -> List[Tuple[int, int]]:
    """(first flat param index, leaf count) per positional argument."""
    import jax
    out: List[Tuple[int, int]] = []
    off = 0
    for a in args:
        n = len(jax.tree_util.tree_leaves(a))
        out.append((off, n))
        off += n
    return out


def _avals(tree: Any) -> Any:
    import jax

    def aval(x):
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype,
                                    sharding=getattr(x, "sharding", None))
    return jax.tree_util.tree_map(aval, tree)


def check_donation(jitted, args: Sequence[Any], donate: Sequence[int],
                   label: str, path: str = _CORE_PATH,
                   hlo: Optional[str] = None) -> List[Violation]:
    """H001: every leaf of each donated argument must be aliased to an
    output in the compiled module. ``args`` may be concrete or avals."""
    from ..launch.hlo_analysis import input_output_aliases
    out: List[Violation] = []
    if hlo is None:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            hlo = jitted.lower(*args).compile().as_text()
    aliased = set(input_output_aliases(hlo).values())
    offsets = _flat_arg_offsets(args)
    for argnum in donate:
        off, n = offsets[argnum]
        missing = [i for i in range(off, off + n) if i not in aliased]
        if missing:
            out.append(Violation(
                "H001", path, 0, label,
                f"donated argument {argnum} ({n} leaves) not aliased "
                f"in the compiled module (flat params {missing} have "
                "no input_output_alias entry) — XLA dropped the "
                "donation and silently double-buffers the array"))
    return out


def check_clean_decode(hlo: str, label: str,
                       path: str = _CORE_PATH) -> List[Violation]:
    """H002: no host callbacks / infeed / dynamic-shape ops."""
    from ..launch.hlo_analysis import custom_call_targets, op_kinds
    out: List[Violation] = []
    for tgt in custom_call_targets(hlo):
        low = tgt.lower()
        if any(m in low for m in _CALLBACK_MARKERS):
            out.append(Violation(
                "H002", path, 0, label,
                f"decode-tick module calls back into the host "
                f"(custom-call target {tgt!r}) — one host block per "
                "decode step"))
    kinds = op_kinds(hlo)
    for op, n in kinds.items():
        if op in _DYNAMIC_OPS or op.startswith(("infeed", "outfeed")):
            out.append(Violation(
                "H002", path, 0, label,
                f"decode-tick module contains {n}x {op} — shape-"
                "dynamic/host-coupled ops defeat the bucketed "
                "executable contract"))
    return out


def check_bank_sharding(compiled, label: str,
                        bank_args: Sequence[int] = (0,),
                        path: str = _CORE_PATH) -> List[Violation]:
    """H003: every leaf of each bank-stacked argument (stacked params,
    KV pool planes) must be sharded ``PartitionSpec('expert', ...)``.
    ``compiled.input_shardings[0]`` preserves per-argument pytree
    structure, so each listed argument's subtree is flattened here."""
    import jax
    out: List[Violation] = []
    args_shardings = compiled.input_shardings[0]
    for argnum in bank_args:
        leaves = jax.tree_util.tree_leaves(args_shardings[argnum])
        for i, s in enumerate(leaves):
            spec = getattr(s, "spec", None)
            lead = spec[0] if spec is not None and len(spec) else None
            if lead != "expert":
                out.append(Violation(
                    "H003", path, 0, label,
                    f"bank arg {argnum} leaf {i} sharded {spec} — "
                    "placement spec requires PartitionSpec('expert', "
                    "...) on the stacked axis"))
    return out


# ---------------------------------------------------------------------------
# the serving dispatches
# ---------------------------------------------------------------------------


def _tiny_hub(kv_layout: str, with_experts: bool = True,
              chunk_len: "int | None" = None):
    """An 8-slot hub on the full 8-device expert mesh, smallest
    geometry the layout allows. Slots start on zero template params —
    enough to lower every executable; real experts are only needed
    when warmup must drive the install scatter."""
    import jax
    from ..configs import get_config
    from ..launch.mesh import make_expert_mesh
    from ..models import build_model
    from ..serve import ExpertHub

    cfg = get_config("smollm-135m").reduced(name=f"hlo-{kv_layout}")
    model = build_model(cfg)
    mesh = make_expert_mesh()
    hub = ExpertHub(model, n_slots=8, max_len=32,
                    len_buckets=(8, 16), batch_buckets=(1, 2),
                    mesh=mesh, kv_layout=kv_layout,
                    chunk_len=chunk_len)
    if with_experts:
        for i in range(8):
            hub.add_expert(f"ex{i}", model.init(jax.random.PRNGKey(i)))
    return hub


def _lower_paged(core) -> List[Tuple[str, Any, tuple, tuple, str, tuple]]:
    """(label, jitted, args(avals), donate_argnums, kind, bank_args)
    for every ladder point of a paged engine; ``bank_args`` are the
    expert-stacked positional arguments H003 checks."""
    import jax.numpy as jnp
    import jax
    E, C = core.n_experts, core.max_len
    nlp, npp_page = core.n_logical, core.page
    p_av = _avals(core.params)
    pool_av = _avals(core.kv_pool)
    cl = core.chunk_len
    out = []
    for Sb in core.len_buckets:
        if cl is not None and Sb > cl:
            continue    # chunked engines never build monolithic
            #             prefills past chunk_len (executable_bounds)
        for Bb in core.batch_buckets:
            toks = jax.ShapeDtypeStruct((E, Bb, Sb), jnp.int32)
            stbl = jax.ShapeDtypeStruct((E, Bb, Sb // npp_page),
                                        jnp.int32)
            out.append((f"paged_prefill[B{Bb},S{Sb}]",
                        core._prefill_fn(Bb, Sb),
                        (p_av, {"tokens": toks}, pool_av, stbl),
                        (2,), "prefill", (0, 2)))
    if cl is not None:
        # the suffix ladder: chunk index k >= 1, chunk_len tokens at
        # static offset k * chunk_len, prefix pages gathered read-only
        ppc = cl // npp_page
        for k in range(1, max(core.len_buckets) // cl):
            for Bb in core.batch_buckets:
                toks = jax.ShapeDtypeStruct((E, Bb, cl), jnp.int32)
                ptbl = jax.ShapeDtypeStruct((E, Bb, k * ppc), jnp.int32)
                stbl = jax.ShapeDtypeStruct((E, Bb, ppc), jnp.int32)
                out.append((f"paged_suffix[B{Bb},k{k}]",
                            core._suffix_fn(Bb, k),
                            (p_av, {"tokens": toks}, pool_av, ptbl,
                             stbl),
                            (2,), "prefill", (0, 2)))
    for Bb in core.batch_buckets:
        tbl = jax.ShapeDtypeStruct((E, Bb, nlp), jnp.int32)
        pos = jax.ShapeDtypeStruct((E, C), jnp.int32)
        t = jax.ShapeDtypeStruct((E,), jnp.int32)
        tok = jax.ShapeDtypeStruct((E, Bb, 1), jnp.int32)
        out.append((f"paged_decode[B{Bb}]", core._decode_fn(Bb),
                    (p_av, pool_av, tbl, pos, t, {"token": tok}),
                    (1,), "decode", (0, 1)))
    m = 2
    es = jax.ShapeDtypeStruct((m,), jnp.int32)
    out.append((f"cow_copy[m{m}]", core._copy_pages_fn(m),
                (pool_av, es, es, es), (0,), "copy", (0,)))
    return out


def run() -> List[Violation]:
    """Lower/compile every serving dispatch and apply H001-H004."""
    import warnings as _w
    import jax
    import jax.numpy as jnp

    _require_devices(8)
    out: List[Violation] = []

    # chunk_len = one page: the hub's 16-bucket prompts split into a
    # chunk-0 prefill plus one suffix chunk, so the warmup ladder
    # drives every executable family the chunked engine owns
    hub = _tiny_hub("paged", chunk_len=8)
    core = hub.bank.core

    # H004 first: warmup drives the whole ladder through the *calling*
    # path the compile counters watch; the AOT lower/compile passes
    # below must not run before the counts are read, or they could
    # perturb the very caches being counted.
    hub.warmup(max_batch=core.batch_buckets[-1], commit=True)
    bounds = core.executable_bounds()
    got_p = core.stats.prefill_compiles
    got_s = core.stats.suffix_compiles
    got_d = core.stats.decode_compiles
    got_i = hub.install_compiles
    if got_p != bounds["prefill"]:
        out.append(Violation(
            "H004", _CORE_PATH, 0, "prefill_ladder",
            f"prefill executables after full warmup: {got_p}, declared "
            f"bound == {bounds['prefill']} "
            f"(executable_bounds: buckets <= chunk_len x batch_buckets)"))
    if got_s != bounds["suffix"]:
        out.append(Violation(
            "H004", _CORE_PATH, 0, "suffix_ladder",
            f"suffix executables after full warmup: {got_s}, declared "
            f"bound == {bounds['suffix']} "
            f"(executable_bounds: chunk indices x batch_buckets)"))
    if got_d != bounds["decode"]:
        out.append(Violation(
            "H004", _CORE_PATH, 0, "decode_ladder",
            f"decode executables after full warmup: {got_d}, declared "
            f"bound == {bounds['decode']} (batch_buckets)"))
    if got_i != 1:
        out.append(Violation(
            "H004", _HUB_PATH, 0, "hub_install",
            f"hub install executables: {got_i}, expected exactly 1 "
            "(slot installs are keyed on bank shape, not expert)"))

    # H001/H002/H003 over the paged ladder
    for label, jitted, args, donate, kind, bank_args in \
            _lower_paged(core):
        with _w.catch_warnings():
            _w.simplefilter("ignore")
            compiled = jitted.lower(*args).compile()
        hlo = compiled.as_text()
        out.extend(check_donation(jitted, args, donate, label, hlo=hlo))
        if kind == "decode":
            out.extend(check_clean_decode(hlo, label))
        out.extend(check_bank_sharding(compiled, label, bank_args))

    # hub slot-install scatter (exists after warmup(commit=True))
    if hub._install is None:
        out.append(Violation(
            "H001", _HUB_PATH, 0, "hub_install",
            "warmup(commit=True) made no commit — cannot lower the "
            "slot install scatter"))
    else:
        iargs = (_avals(core.params), _avals(hub.catalog[0].params),
                 jax.ShapeDtypeStruct((), jnp.int32))
        out.extend(check_donation(hub._install, iargs, (0,),
                                  "hub_install", path=_HUB_PATH))

    # ring layout: the non-paged decode donates its dense cache the
    # same way — template-param hub, lowering only, no warmup needed
    ring = _tiny_hub("ring", with_experts=False)
    rcore = ring.bank.core
    p_av = _avals(rcore.params)
    Bb = rcore.batch_buckets[0]
    Sb = rcore.len_buckets[0]
    toks = jax.ShapeDtypeStruct((rcore.n_experts, Bb, Sb), jnp.int32)
    _, cache_av = jax.eval_shape(rcore._prefill_fn(Bb, Sb),
                                 _avals(rcore.params), {"tokens": toks})
    tok = jax.ShapeDtypeStruct((rcore.n_experts, Bb, 1), jnp.int32)
    args = (p_av, cache_av, {"token": tok})
    jitted = rcore._decode_fn(Bb)
    with _w.catch_warnings():
        _w.simplefilter("ignore")
        compiled = jitted.lower(*args).compile()
    hlo = compiled.as_text()
    out.extend(check_donation(jitted, args, (1,),
                              f"ring_decode[B{Bb}]", hlo=hlo))
    out.extend(check_clean_decode(hlo, f"ring_decode[B{Bb}]"))
    out.extend(check_bank_sharding(compiled, f"ring_decode[B{Bb}]",
                                   (0, 1)))

    # speculative ladder: a dedicated E=1 spec engine (ring, k=2)
    # driven through the *calling* path via generate(). max_len == 16
    # makes the admission grid split cleanly on the no-wrap gate
    # (Sb + steps + k <= max_len): Sb=8 waves speculate — only the
    # verify family compiles — while Sb=16 waves are gate-blocked and
    # fall back to the plain decode family, so after the grid BOTH
    # ladders must sit exactly at their declared bounds.
    import numpy as np
    from ..configs import get_config
    from ..models import build_model
    from ..serve import ExpertEngine

    scfg = get_config("smollm-135m").reduced(name="hlo-spec")
    smodel = build_model(scfg)
    seng = ExpertEngine(smodel, smodel.init(jax.random.PRNGKey(0)),
                        max_len=16, min_len_bucket=8,
                        batch_buckets=(1, 2), speculate_k=2,
                        draft="table")
    score = seng.core
    for Sb_g, max_new in ((8, 4), (16, 2)):
        for Bb_g in score.batch_buckets:
            seng.generate(np.full((Bb_g, Sb_g), 3, np.int32), max_new)
    sbounds = score.executable_bounds()
    got_v = score.stats.verify_compiles
    got_fd = score.stats.decode_compiles
    if got_v != sbounds["verify"]:
        out.append(Violation(
            "H004", _CORE_PATH, 0, "verify_ladder",
            f"verify executables after the speculative grid: {got_v}, "
            f"declared bound == {sbounds['verify']} "
            "(executable_bounds: batch_buckets x one engine-fixed k)"))
    if got_fd != sbounds["decode"]:
        out.append(Violation(
            "H004", _CORE_PATH, 0, "spec_fallback_decode_ladder",
            f"decode executables after gate-blocked (wrap-risk) waves: "
            f"{got_fd}, declared bound == {sbounds['decode']} "
            "— speculation must not mint extra decode variants"))
    if score.stats.spec_fallback_waves == 0:
        out.append(Violation(
            "H004", _CORE_PATH, 0, "spec_fallback_gate",
            "no admission in the wrap-risk grid was gate-blocked — "
            "the no-wrap gate is not exercising the fallback decode "
            "family, so its bound above proved nothing"))

    # H001/H002 over the ring verify executable itself (E=1 engine
    # built without a mesh, so H003 does not apply)
    vk = score.speculate_k
    vBb = score.batch_buckets[0]
    vSb = score.len_buckets[0]
    vE, vC = score.n_experts, score.max_len
    sp_av = _avals(score.params)
    vtoks = jax.ShapeDtypeStruct((vE, vBb, vSb), jnp.int32)
    _, wave_cache_av = jax.eval_shape(score._prefill_fn(vBb, vSb),
                                      sp_av, {"tokens": vtoks})
    vargs = (sp_av,
             {"k": wave_cache_av["k"], "v": wave_cache_av["v"]},
             jax.ShapeDtypeStruct((vE, vBb, vC), jnp.int32),  # row_pos
             jax.ShapeDtypeStruct((vE, vBb), jnp.int32),      # row_t
             jax.ShapeDtypeStruct((vE, vBb), jnp.int32),      # tok
             jax.ShapeDtypeStruct((vE, vBb), jnp.int32),      # cap
             _avals(score.draft_state))
    vlabel = f"ring_verify[B{vBb},k{vk}]"
    vjit = score._verify_fn(vBb, vk)
    with _w.catch_warnings():
        _w.simplefilter("ignore")
        vhlo = vjit.lower(*vargs).compile().as_text()
    out.extend(check_donation(vjit, vargs, (1,), vlabel, hlo=vhlo))
    out.extend(check_clean_decode(vhlo, vlabel))
    return out
