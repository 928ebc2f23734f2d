"""Chip-less program linter (paddle_tpu.analysis): detectors over jaxpr /
TPU StableHLO / AOT v5e HLO, the known-bad regression corpus, and the
model-zoo CI gate (tools/lint_programs.py).

The corpus tests are the regression teeth: each corpus program re-creates
a hazard class this repo actually shipped (the PR-1 lse/dvec broadcast,
the ROADMAP relayout sandwich, ...) and the linter must flag it with the
RIGHT detector id — so a detector that silently stops firing fails here,
not on a chip three PRs later.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.join(os.path.dirname(__file__), os.pardir)

from paddle_tpu import analysis
from paddle_tpu.analysis import hlo as H
from paddle_tpu.analysis.corpus import CORPUS, build_corpus_program
from paddle_tpu.analysis.findings import Finding


def _skip_if_no_topology():
    try:
        from paddle_tpu.core.aot_tpu import tpu_topology

        tpu_topology()
    except Exception as e:  # pragma: no cover - environment-dependent
        pytest.skip(f"no chip-less TPU topology available: {e}")


# ---------------------------------------------------------------------------
# findings


def test_finding_severity_validated_and_json_stable():
    f = Finding(detector="host-sync", severity="error", program="p",
                message="m", bytes=3, where="w", fingerprint="abc")
    d = f.as_dict()
    assert d == {"detector": "host-sync", "severity": "error",
                 "program": "p", "message": "m", "bytes": 3,
                 "where": "w", "fingerprint": "abc"}
    assert "host-sync" in f.format() and "ERROR" in f.format()
    with pytest.raises(ValueError):
        Finding(detector="x", severity="fatal", program="p", message="m")


# ---------------------------------------------------------------------------
# HLO / StableHLO text parsers


_HLO_SNIPPET = """\
HloModule jit_fn, entry_computation_layout={(f32[2,8,8,4]{3,0,2,1:T(8,128)}, f32[4]{0:T(256)})->(f32[2,8,8,4]{3,2,1,0:T(8,128)}, f32[]{:T(128)})}, input_output_alias={ {0}: (0, {}, may-alias) }

ENTRY %main (p0: f32[2,8,8,4], p1: f32[4]) -> (f32[2,8,8,4], f32[]) {
  %p0 = f32[2,8,8,4]{3,0,2,1:T(8,128)} parameter(0)
  %p1 = f32[4]{0:T(256)} parameter(1)
  %copy.1 = f32[2,8,8,4]{3,2,1,0:T(8,128)} copy(f32[2,8,8,4]{3,0,2,1:T(8,128)} %p0)
  %cc = f32[2,8,8,4]{3,2,1,0:T(8,128)} custom-call(f32[2,8,8,4]{3,2,1,0:T(8,128)} %copy.1), custom_call_target="tpu_custom_call", metadata={op_name="x"}
  %copy.2 = f32[2,8,8,4]{3,0,2,1:T(8,128)} copy(f32[2,8,8,4]{3,2,1,0:T(8,128)} %cc)
  %copy.3 = f32[2,8,8,4]{3,2,1,0:T(8,128)} copy(f32[2,8,8,4]{3,0,2,1:T(8,128)} %copy.2)
  %sum = f32[]{:T(128)} constant(0)
  ROOT %tup = (f32[2,8,8,4]{3,2,1,0:T(8,128)}, f32[]{:T(128)}) tuple(%copy.3, %sum)
}
"""


def test_hlo_parse_shapes_layouts_and_operands():
    s = H.parse_shape("f32[2,56,56,64]{3,0,2,1:T(8,128)S(1)}")
    assert (s.dtype, s.dims, s.perm) == ("f32", (2, 56, 56, 64), "3,0,2,1")
    assert s.bytes == 2 * 56 * 56 * 64 * 4
    assert H.parse_shape("bf16[8]").perm == ""
    instrs = H.entry_instructions(_HLO_SNIPPET)
    by = {i.name: i for i in instrs}
    assert by["cc"].opcode == "custom-call"
    assert by["cc"].operand_names == ["copy.1"]
    # metadata attrs after the close paren must not contribute operands
    assert "x" not in by["cc"].operand_names
    assert by["copy.1"].operands[0][0].perm == "3,0,2,1"
    assert by["tup"].is_root


def test_hlo_parse_entry_layout_and_alias():
    params, outs = H.parse_entry_layout(_HLO_SNIPPET)
    assert [p.dims for p in params] == [(2, 8, 8, 4), (4,)]
    assert [o.dims for o in outs] == [(2, 8, 8, 4), ()]
    assert H.parse_input_output_alias(_HLO_SNIPPET) == {0: 0}
    assert H.parse_input_output_alias("HloModule x") == {}


def test_relayout_detector_on_synthetic_hlo():
    """The copy-pair bracketing the pinned custom call is found on both
    sides; the downstream same-destination copy.3 (a plain memory-space
    move in real dumps) is not double-counted as draining the call."""
    from paddle_tpu.analysis.capture import ProgramArtifacts
    from paddle_tpu.analysis.detectors import detect_relayout_copies

    art = ProgramArtifacts(name="synthetic", jaxpr=None, stablehlo="",
                           hlo=_HLO_SNIPPET, cost={})
    found = detect_relayout_copies(art)
    wheres = sorted(f.where for f in found)
    assert wheres == ["cc->copy.2", "copy.1->cc"]
    assert all(f.detector == "relayout-copy-pair" for f in found)
    assert all(f.bytes == 2 * 8 * 8 * 4 * 4 for f in found)


# ---------------------------------------------------------------------------
# the known-bad regression corpus: each program must trip its detector


@pytest.mark.parametrize("name", sorted(CORPUS))
def test_corpus_program_trips_its_detector(name):
    _skip_if_no_topology()
    builder, expected_detector = CORPUS[name]
    art = build_corpus_program(name)
    if expected_detector is None:
        # bytes-gated corpus entries (gqa_full_pool) are structurally
        # healthy by design — the dedicated bytes-gate test below is
        # their teeth; here just pin that they compile and analyze
        assert not art.compile_error
        return
    findings = analysis.run_detectors(art)
    hit = [f for f in findings if f.detector == expected_detector]
    assert hit, (
        f"corpus program {name!r} must be flagged by {expected_detector}; "
        f"got {[f.detector for f in findings]}")
    assert all(f.program == art.name and f.fingerprint == art.fingerprint
               for f in hit)


def test_corpus_gqa_full_pool_trips_bytes_gate():
    """ISSUE 12 satellite: a full-H_q pool on a GQA config must FAIL the
    gqa_decode bytes/step tolerance rather than silently passing — the
    corpus program carries the zoo entry's name, so the verdict lands on
    the banked grouped baseline (the page stream is H_q/H_kv = 4x it).
    No detector arm exists for this hazard: the bytes gate IS the
    check."""
    _skip_if_no_topology()
    from paddle_tpu.analysis.corpus import corpus_extra_bytes

    art = build_corpus_program("gqa_full_pool")
    assert art.name == "gqa_decode"  # deliberately the zoo entry's slot
    extra = corpus_extra_bytes("gqa_full_pool")
    assert extra > 0  # the analytic stream is what busts the budget
    bad = analysis.ZooResult(
        name=art.name, artifacts=art, findings=[],
        bytes_per_step=art.bytes_per_step + extra, flops_per_step=0.0)
    verdicts, failed = analysis.gate(
        [bad], analysis.default_baseline_path())
    assert failed
    v = [x for x in verdicts
         if x["metric"] == "gqa_decode_aot_bytes_per_step"]
    assert v and v[0]["verdict"] == "fail"
    # ~4x the banked grouped bytes: the full-head pool pays H_q/H_kv x
    assert v[0]["current"] > 3.0 * v[0]["baseline"]


def test_corpus_broadcast_lse_reports_materialized_bytes():
    """The PR-1 bug class: the [512] lse vector broadcast to [512,128]
    as a custom-call operand is charged at its full materialized size."""
    _skip_if_no_topology()
    art = build_corpus_program("broadcast_lse")
    hit = [f for f in analysis.run_detectors(art)
           if f.detector == "broadcast-operand"]
    assert hit[0].bytes == 512 * 128 * 4
    assert hit[0].severity == "error"


def test_corpus_missed_donation_sized_and_donated_arm_clean():
    """The un-donated state shows one finding per eligible buffer at the
    buffer's byte size; actually donating the same state clears them."""
    _skip_if_no_topology()
    from paddle_tpu.analysis.capture import capture_fn

    art = build_corpus_program("missed_donation")
    hit = [f for f in analysis.run_detectors(art)
           if f.detector == "missed-donation"]
    assert len(hit) == 3  # three eligible state buffers, none aliased
    assert all(f.bytes == 256 * 256 * 4 for f in hit)

    def fn(state, x):
        return [s + x for s in state], jnp.sum(x)

    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    donated = capture_fn(fn, [a, a, a], a, donate_argnums=(0,),
                         name="donated")
    assert not [f for f in analysis.run_detectors(donated)
                if f.detector == "missed-donation"]


def test_master_weight_update_idiom_not_flagged():
    """AMP master weights: a bf16 grad cast to f32 to update f32 params
    joins an equally-sized already-f32 tensor — the f32 write-back is the
    params' own dtype, not a promotion leak (the resnet50_train zoo
    program relies on this staying clean)."""
    _skip_if_no_topology()
    from paddle_tpu.analysis.capture import capture_fn

    def step(p, v, g_bf16):
        g = g_bf16.astype(jnp.float32)
        v2 = 0.9 * v + g
        return p - 0.1 * v2, v2

    f32 = jax.ShapeDtypeStruct((512, 512), jnp.float32)
    bf = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
    art = capture_fn(step, f32, f32, bf, name="master_weight")
    assert not [f for f in analysis.run_detectors(art)
                if f.detector == "dtype-promotion"]


def test_missed_donation_indices_survive_unused_arg():
    """jit would normally PRUNE an unused arg from the executable's
    entry parameters, shifting every index the analyzer computed from
    the python signature (trace_tpu pins them with keep_unused).  The
    detector must anchor the findings on the state leaves, not drift
    onto the feed."""
    _skip_if_no_topology()
    from paddle_tpu.analysis.capture import capture_fn

    def fn(unused, state, x):
        return [s + x for s in state], jnp.sum(x)

    u = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    art = capture_fn(fn, u, [a, a], a, donatable_argnums=(1,),
                     name="unused_arg")
    hit = [f for f in analysis.run_detectors(art)
           if f.detector == "missed-donation"]
    assert len(hit) == 2  # both state leaves, nothing anchored elsewhere
    assert all(f.bytes == 256 * 256 * 4 for f in hit)
    assert {f.where.split(" ")[1] for f in hit} == {"1", "2"}


def test_own_kernels_clean_of_corpus_bug_classes():
    """The tentpole's 'asserted dead in our own kernels' clause: the
    flash-attention and paged-decode custom calls must show zero
    broadcast-materialized operands and zero relayout copy-pairs."""
    _skip_if_no_topology()
    from paddle_tpu.analysis.capture import capture_fn
    from paddle_tpu.kernels.flash_attention import flash_attention
    from paddle_tpu.kernels.paged_attention import paged_decode_attention

    B, H_, S, D = 2, 4, 256, 128
    qkv = jax.ShapeDtypeStruct((B, H_, S, D), jnp.float32)
    art = capture_fn(lambda q, k, v: flash_attention(q, k, v, causal=True),
                     qkv, qkv, qkv, name="flash_fwd")
    bad = [f for f in analysis.run_detectors(art)
           if f.detector in ("broadcast-operand", "relayout-copy-pair")]
    assert not bad, [f.format() for f in bad]

    ps, maxp = 16, 8
    P = B * maxp
    q = jax.ShapeDtypeStruct((B, H_, 1, D), jnp.float32)
    kp = jax.ShapeDtypeStruct((H_, P, ps, D), jnp.float32)
    tb = jax.ShapeDtypeStruct((B, maxp), jnp.int32)
    ln = jax.ShapeDtypeStruct((B,), jnp.int32)
    art = capture_fn(
        lambda q, k, v, t, l: paged_decode_attention(
            q, k, v, t, l, impl="pallas"),
        q, kp, kp, tb, ln, name="paged")
    bad = [f for f in analysis.run_detectors(art)
           if f.detector in ("broadcast-operand", "relayout-copy-pair")]
    assert not bad, [f.format() for f in bad]


def test_corpus_host_callback_counted_once():
    """One pure_callback is ONE hazard: the jaxpr prim scan and the
    StableHLO custom-call marker scan must not both report the same
    callback — a double count would bank 2x and make the gate's
    new-finding comparison jax-version-sensitive."""
    _skip_if_no_topology()
    art = build_corpus_program("host_callback")
    hit = [f for f in analysis.run_detectors(art)
           if f.detector == "host-sync"]
    assert len(hit) == 1
    assert hit[0].where == "pure_callback"


def test_capture_time_hazards_python_scalar_feed_and_unhashable_key(
        monkeypatch):
    from paddle_tpu import flags as fl
    from paddle_tpu.analysis.capture import _capture_time_hazards

    hz = _capture_time_hazards("p", {"lr": 0.1, "x": np.zeros(3)}, "fp")
    assert [f.where for f in hz] == ["feed:lr"]
    assert hz[0].detector == "recompile-hazard"
    monkeypatch.setattr(fl, "trace_key", lambda: ["not", "hashable"])
    hz = _capture_time_hazards("p", {}, "fp")
    assert [f.where for f in hz] == ["flags.trace_key"]
    assert hz[0].severity == "error"


def test_capture_executor_unhashable_key_reports_not_crashes(monkeypatch):
    """The executor's own cache lookup hashes flags.trace_key() before
    anything else — a non-hashable key must come back as the
    recompile-hazard finding the detector advertises, not a TypeError."""
    _skip_if_no_topology()
    import paddle_tpu as fluid
    from paddle_tpu import flags as fl, layers

    fluid.reset_default_env()
    x = layers.data("x", [8, 8], dtype="float32")
    loss = layers.mean(layers.fc(x, size=4))
    fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    monkeypatch.setattr(fl, "trace_key", lambda: ["not", "hashable"])
    art = analysis.capture_executor(
        exe, feed={"x": np.zeros((2, 8, 8), "float32")},
        fetch_list=[loss], name="unhashable")
    assert art.compile_error  # nothing was compiled
    findings = analysis.run_detectors(art)
    assert any(f.detector == "recompile-hazard"
               and f.where == "flags.trace_key" for f in findings)


def test_capture_executor_current_tree_is_clean():
    """The executor seam: the exact chip program a small train step runs
    (same cache entry, state donation included) lints clean — donation is
    realized, no weak types, no host syncs."""
    _skip_if_no_topology()
    import paddle_tpu as fluid
    from paddle_tpu import layers

    fluid.reset_default_env()
    x = layers.data("x", [16, 16], dtype="float32")
    loss = layers.mean(layers.fc(x, size=8))
    fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    art = analysis.capture_executor(
        exe, feed={"x": np.zeros((4, 16, 16), "float32")},
        fetch_list=[loss], name="fc_train")
    assert art.hlo and art.bytes_per_step > 0
    assert art.compile_error == ""
    findings = analysis.run_detectors(art)
    assert not findings, [f.format() for f in findings]


# ---------------------------------------------------------------------------
# gate logic (pure — fabricated results, no compiles)


def _zr(name, counts, bytes_per_step, flops=0.0):
    from paddle_tpu.analysis.capture import ProgramArtifacts
    from paddle_tpu.analysis.zoo import ZooResult

    art = ProgramArtifacts(name=name, jaxpr=None, stablehlo="", hlo="",
                           cost={}, fingerprint="f" * 12)
    findings = [
        Finding(detector=det, severity="warning", program=name, message="x")
        for det, n in counts.items() for _ in range(n)
    ]
    return ZooResult(name=name, artifacts=art, findings=findings,
                     bytes_per_step=bytes_per_step, flops_per_step=flops)


def _bank_doc(tmp_path, programs, tolerance=0.02):
    p = tmp_path / "base.json"
    p.write_text(json.dumps(
        {"tolerance": tolerance, "programs": programs}))
    return str(p)


def test_gate_new_finding_fails(tmp_path):
    base = _bank_doc(tmp_path, {
        "a": {"findings": {}, "bytes_per_step": 100.0}})
    verdicts, failed = analysis.gate(
        [_zr("a", {"host-sync": 1}, 100.0)], base)
    assert failed
    assert any(v["metric"] == "a_findings[host-sync]"
               and v["verdict"] == "fail" for v in verdicts)


def test_gate_bytes_regression_fails_and_within_tol_passes(tmp_path):
    base = _bank_doc(tmp_path, {
        "a": {"findings": {}, "bytes_per_step": 100.0}})
    _, failed = analysis.gate([_zr("a", {}, 101.0)], base)
    assert not failed  # +1% within the 2% tolerance
    verdicts, failed = analysis.gate([_zr("a", {}, 110.0)], base)
    assert failed
    assert any("bytes_per_step" in v["metric"] and v["verdict"] == "fail"
               for v in verdicts)


def test_gate_unbanked_program_fails_and_fewer_findings_pass(tmp_path):
    base = _bank_doc(tmp_path, {
        "a": {"findings": {"host-sync": 2}, "bytes_per_step": 100.0}})
    verdicts, failed = analysis.gate(
        [_zr("a", {"host-sync": 1}, 100.0), _zr("new", {}, 1.0)], base)
    assert failed  # 'new' has no banked entry
    assert any(v["metric"] == "new_findings" and v["verdict"] == "fail"
               for v in verdicts)
    # strictly-fewer findings is a pass that nudges a re-bank
    better = [v for v in verdicts if v["metric"] == "a_findings[host-sync]"]
    assert better and better[0]["verdict"] == "pass"
    assert "re-bank" in better[0]["reason"]


def test_gate_fails_and_bank_refuses_on_compile_error(tmp_path):
    """A program the v5e pipeline rejects analyzed NOTHING HLO-side —
    bytes collapse to 0, which lower-is-better would wave through.  The
    gate must fail it and bank must refuse to freeze it."""
    base = _bank_doc(tmp_path, {
        "a": {"findings": {}, "bytes_per_step": 100.0}})
    r = _zr("a", {}, 0.0)
    r.artifacts.compile_error = "Mosaic rejected the kernel"
    verdicts, failed = analysis.gate([r], base)
    assert failed
    assert any(v["metric"] == "a_compile" and v["verdict"] == "fail"
               for v in verdicts)
    with pytest.raises(ValueError, match="compile failed"):
        analysis.bank([r], str(tmp_path / "out.json"))


def test_run_zoo_validates_detector_names_before_capturing():
    with pytest.raises(KeyError, match="unknown detector"):
        analysis.run_zoo(["paged_decode"], detectors=["host-synk"])


def test_gate_require_all_fails_on_vanished_banked_program(tmp_path):
    """Deleting/renaming a zoo entry must not silently shrink CI
    coverage: an unfiltered run gates banked-but-not-run programs."""
    base = _bank_doc(tmp_path, {
        "a": {"findings": {}, "bytes_per_step": 100.0},
        "b": {"findings": {}, "bytes_per_step": 50.0}})
    results = [_zr("a", {}, 100.0)]
    _, failed = analysis.gate(results, base)  # filtered run: fine
    assert not failed
    verdicts, failed = analysis.gate(results, base, require_all=True)
    assert failed
    assert any(v["metric"] == "b_coverage" and v["verdict"] == "fail"
               for v in verdicts)


def test_zoo_builder_sandbox_preserves_caller_env():
    """run_zoo is public API: building a zoo model must not clobber the
    caller's default program, scope, or name counters."""
    import paddle_tpu as fluid
    from paddle_tpu.analysis.zoo import _fresh_env

    fluid.reset_default_env()
    fluid.layers.data("keepme", [4], dtype="float32")
    main_before = fluid.default_main_program()
    scope_before = fluid.global_scope()
    with _fresh_env() as fl:
        assert fl.default_main_program() is not main_before
        assert fl.global_scope() is not scope_before
        fl.layers.data("inner", [2], dtype="float32")
    assert fluid.default_main_program() is main_before
    assert fluid.global_scope() is scope_before
    names = list(main_before.desc.block(0).vars)
    assert "keepme" in names and "inner" not in names


def test_gate_injected_corpus_programs_each_fail(tmp_path):
    """ISSUE acceptance: every known-bad corpus program splices into a
    zoo run as an UNBANKED program carrying findings — the gate must fail
    for each one."""
    base = _bank_doc(tmp_path, {
        "a": {"findings": {}, "bytes_per_step": 100.0},
        "gqa_decode": {"findings": {}, "bytes_per_step": 100.0}})
    clean = _zr("a", {}, 100.0)
    for name, (_, det) in sorted(CORPUS.items()):
        if det is None:
            # bytes-gated corpus entry: splices in UNDER the banked zoo
            # entry's own name and busts its bytes tolerance instead of
            # carrying a finding (the full-H_q-pool hazard has none)
            bad = _zr("gqa_decode", {}, 400.0)
        else:
            bad = _zr(f"corpus_{name}", {det: 1}, 5.0)
        _, failed = analysis.gate([clean, bad], base)
        assert failed, f"gate must trip on injected corpus {name!r}"


# ---------------------------------------------------------------------------
# the CLI end-to-end (cheapest zoo program only; the full-zoo gate runs
# as tools/lint_programs.py --gate in CI and in the slow tier below)


def _lint_main(argv):
    sys.path.insert(0, os.path.abspath(REPO))
    try:
        from tools.lint_programs import main

        return main(argv)
    finally:
        sys.path.pop(0)


def test_lint_cli_list_and_bank_refusal(tmp_path, capsys):
    assert _lint_main(["--list"]) == 0
    out = capsys.readouterr().out
    assert "paged_decode" in out and "broadcast_lse" in out
    # banking a filtered or injected run must refuse (exit 2), not
    # silently narrow the baseline — a --detectors subset would bank
    # under-counted findings that the next full run reports as "new"
    assert _lint_main(["--programs", "paged_decode", "--bank",
                       "--baseline", str(tmp_path / "b.json")]) == 2
    assert _lint_main(["--detectors", "host-sync", "--bank",
                       "--baseline", str(tmp_path / "b.json")]) == 2
    # --gate with a detector subset would let the OTHER detectors'
    # regressions gate green — refuse, same as --bank
    assert _lint_main(["--detectors", "host-sync", "--gate"]) == 2
    capsys.readouterr()


@pytest.mark.parametrize("argv", [
    ["--programs", "paged_decode"], ["--detectors", "host-sync"],
    ["--inject", "weak_type"]], ids=["programs", "detectors", "inject"])
def test_lint_cli_refuses_a_narrowed_bank_before_it_compiles_the_zoo(
        argv, tmp_path, monkeypatch, capsys):
    """--bank of a filtered or injected run is refused (exit 2) before the
    zoo is lowered and compiled, not after: with --detectors alone the
    whole zoo used to run first (~1 min) only to be refused."""
    _skip_if_no_topology()

    def no_zoo(*a, **k):
        raise AssertionError("the zoo ran before the refusal")

    monkeypatch.setattr(analysis, "run_zoo", no_zoo)
    assert _lint_main(argv + ["--bank", "--baseline",
                              str(tmp_path / "b.json")]) == 2
    assert "refusing to --bank" in capsys.readouterr().err
    assert not (tmp_path / "b.json").exists()


def test_lint_cli_gate_round_trip_and_regression(tmp_path, capsys):
    """bank -> re-gate passes; injected corpus program exits 3; a banked
    baseline with smaller bytes/step (i.e. the tree regressed) exits 3."""
    _skip_if_no_topology()
    base = str(tmp_path / "zoo.json")
    rc = _lint_main(["--programs", "paged_decode", "--json",
                     str(tmp_path / "r.json")])
    assert rc == 0
    run = json.loads((tmp_path / "r.json").read_text())
    prog = run["programs"]["paged_decode"]
    assert prog["finding_counts"] == {}  # current tree lints clean
    assert prog["bytes_per_step"] > 0

    doc = {"tolerance": 0.02, "programs": {"paged_decode": {
        "findings": {}, "bytes_per_step": prog["bytes_per_step"],
        "flops_per_step": prog["flops_per_step"]}}}
    (tmp_path / "zoo.json").write_text(json.dumps(doc))
    assert _lint_main(["--programs", "paged_decode",
                       "--baseline", base, "--gate"]) == 0

    # an injected known-bad program trips the gate end-to-end
    assert _lint_main(["--programs", "paged_decode", "--inject",
                       "weak_type", "--baseline", base, "--gate"]) == 3

    # a bytes/step rise past tolerance trips the gate
    doc["programs"]["paged_decode"]["bytes_per_step"] = (
        prog["bytes_per_step"] * 0.5)
    (tmp_path / "zoo.json").write_text(json.dumps(doc))
    assert _lint_main(["--programs", "paged_decode",
                       "--baseline", base, "--gate"]) == 3
    capsys.readouterr()


def test_lint_cli_gate_missing_baseline_is_usage_error(tmp_path, capsys):
    _skip_if_no_topology()
    rc = _lint_main(["--programs", "paged_decode", "--gate",
                     "--baseline", str(tmp_path / "nope.json")])
    assert rc == 2
    capsys.readouterr()


def test_ci_gate_exit_code_contract_shared_with_serve_bench(
        tmp_path, capsys):
    """README 'CI gates': all three gate tools exit 2 on usage errors
    (not 0, not a traceback) so CI wiring can tell 'gate broken' from
    'tree regressed' (exit 3)."""
    sys.path.insert(0, os.path.abspath(REPO))
    try:
        from tools.obsdump import main as obsdump_main
        from tools.serve_bench import main as bench_main
    finally:
        sys.path.pop(0)
    assert bench_main(["--gate"]) == 2  # --gate without --baseline
    assert bench_main(["--baseline", str(tmp_path / "nope.json")]) == 2
    assert obsdump_main([str(tmp_path), "--baseline",
                         str(tmp_path / "nope.json"), "--gate"]) == 2
    capsys.readouterr()


# ---------------------------------------------------------------------------
# the full zoo vs the committed baseline (the per-PR CI gate itself):
# resnet50+transformer AOT compiles make this the one heavy test here


@pytest.mark.slow
def test_full_zoo_gate_green_against_committed_baseline(capsys):
    _skip_if_no_topology()
    rc = _lint_main(["--gate"])
    assert rc == 0
    capsys.readouterr()


def test_gqa_decode_banked_ratio_and_coverage():
    """ISSUE 12 acceptance: the banked gqa_decode entry's KV bytes/step
    sits within 10% of H_kv/H_q x the paged_decode baseline (the
    grouped kernel streams each page once per KV head, not per query
    head), int8 pages price ~1/4 of that again (fp32 -> int8 elements;
    '2x on top of bf16'), and the entry is under require_all coverage —
    deleting it from the zoo fails the gate instead of shrinking CI."""
    with open(analysis.default_baseline_path()) as f:
        progs = json.load(f)["programs"]
    assert "gqa_decode" in progs
    cfg = progs["gqa_decode"]["config"]
    want = cfg["kv_heads"] / cfg["heads"]  # H_kv / H_q
    ratio = (progs["gqa_decode"]["bytes_per_step"]
             / progs["paged_decode"]["bytes_per_step"])
    assert abs(ratio - want) / want < 0.10, ratio
    # the further dtype arms of the same analytic model: int8 at 1/4
    # the fp32 stream (+ per-page scale reads), i.e. half of bf16 again
    from paddle_tpu.kernels.paged_attention import attention_bytes_per_step

    args = (4, cfg["max_pages"], cfg["page_size"], cfg["heads"],
            cfg["head_dim"])
    fp32 = attention_bytes_per_step("pallas", *args, num_kv_heads=2,
                                    dtype="float32")
    bf16 = attention_bytes_per_step("pallas", *args, num_kv_heads=2,
                                    dtype="bfloat16")
    i8 = attention_bytes_per_step("pallas", *args, num_kv_heads=2,
                                  dtype="int8")
    assert 0.24 <= i8 / fp32 <= 0.27
    assert 0.49 <= i8 / bf16 <= 0.52
    # require_all: a run missing the banked gqa_decode fails coverage
    others = [_zr(n, e.get("findings", {}), e["bytes_per_step"])
              for n, e in progs.items() if n != "gqa_decode"]
    verdicts, failed = analysis.gate(
        others, analysis.default_baseline_path(), require_all=True)
    assert failed
    assert any(v["metric"] == "gqa_decode_coverage"
               and v["verdict"] == "fail" for v in verdicts)


# ---------------------------------------------------------------------------
# satellite: resolve_paged_impl fallbacks are counted, not just logged


def test_paged_fallback_counted_and_metered(monkeypatch):
    from paddle_tpu import flags as fl
    from paddle_tpu import observability as obs
    from paddle_tpu.kernels import paged_attention as pa

    before = pa.fallback_count()
    # in-envelope explicit pallas resolves without counting
    assert pa.resolve_paged_impl("interpret", 16, 128, jnp.float32) \
        == "interpret"
    assert pa.fallback_count() == before
    # a CPU host's auto->reference is expected, not a fallback
    assert pa.resolve_paged_impl("auto", 16, 128, jnp.float32) \
        == "reference"
    assert pa.fallback_count() == before
    # auto on a TPU host wanted pallas: out-of-envelope degradation to
    # the reference gather must count (in-envelope must not)
    monkeypatch.setattr(pa, "on_tpu", lambda: True)
    assert pa.resolve_paged_impl("auto", 16, 96, jnp.float32) \
        == "reference"
    assert pa.fallback_count() == before + 1
    assert pa.resolve_paged_impl("auto", 16, 128, jnp.float32) == "pallas"
    assert pa.fallback_count() == before + 1
    monkeypatch.setattr(pa, "on_tpu", lambda: False)
    before = pa.fallback_count()
    # out-of-envelope explicit pallas falls back AND counts
    assert pa.resolve_paged_impl("pallas", 16, 96, jnp.float32) \
        == "reference"
    assert pa.fallback_count() == before + 1
    # with observability on, the labeled counter records it too
    obs.default_registry().reset()
    old = fl.flag("FLAGS_observability")
    fl.set_flags({"FLAGS_observability": True})
    try:
        pa.resolve_paged_impl("pallas", 16, 96, jnp.float32)
        snap = obs.default_registry().snapshot()["metrics"]
        fb = [m for m in snap
              if m["name"] == "paddle_tpu_serving_fallback"]
        assert fb and fb[0]["series"][0]["labels"] == {
            "kernel": "paged_attention"}
        assert fb[0]["series"][0]["value"] == 1
    finally:
        fl.set_flags({"FLAGS_observability": old})
        obs.default_registry().reset()
    assert pa.fallback_count() == before + 2


# ---------------------------------------------------------------------------
# kernel-interior tier (ISSUE 14): VMEM pricing + the two new detectors


def test_tile_padded_bytes_pads_to_whole_tiles():
    """The estimator prices buffers the way Mosaic stores them: last two
    dims padded to whole (sublane, lane) tiles per dtype."""
    from paddle_tpu.analysis.pallas import tile_padded_bytes

    assert tile_padded_bytes((8, 128), "float32") == 8 * 128 * 4
    assert tile_padded_bytes((8, 1), "float32") == 8 * 128 * 4
    assert tile_padded_bytes((1, 1, 3, 130), "float32") == 8 * 256 * 4
    assert tile_padded_bytes((9, 128), "bfloat16") == 16 * 128 * 2
    assert tile_padded_bytes((1, 128), "int8") == 32 * 128
    assert tile_padded_bytes((128,), "float32") == 8 * 128 * 4


def _traced_pallas_eqns(fn, *args):
    from paddle_tpu import flags as fl
    from paddle_tpu.analysis import pallas as AP

    with fl.tpu_trace_scope(True):
        jx = jax.make_jaxpr(fn)(*args)
    return list(AP.iter_pallas_calls(jx))


def test_kernel_vmem_bytes_prices_the_paged_kernel():
    """The traced paged-decode pallas_call prices exactly as the kernel
    allocates: double-buffered padded q/k/v/o blocks + fp32 softmax
    scratch in VMEM, the scalar-prefetched page table/lengths in SMEM."""
    from paddle_tpu.analysis import pallas as AP
    from paddle_tpu.kernels.paged_attention import paged_decode_attention

    B, H, D, ps, maxp = 4, 8, 128, 16, 32
    P = B * maxp
    q = jax.ShapeDtypeStruct((B, H, 1, D), jnp.float32)
    kp = jax.ShapeDtypeStruct((H, P, ps, D), jnp.float32)
    tb = jax.ShapeDtypeStruct((B, maxp), jnp.int32)
    ln = jax.ShapeDtypeStruct((B,), jnp.int32)
    eqns = _traced_pallas_eqns(
        lambda q, k, v, t, l: paged_decode_attention(
            q, k, v, t, l, impl="pallas"), q, kp, kp, tb, ln)
    assert len(eqns) == 1
    cost = AP.kernel_cost(eqns[0])
    # blocks: q/o (1,1,8,128) fp32 = 4 KB each, k/v (1,1,16,128) = 8 KB
    # each, double-buffered; scratch: two (8,1)->one tile each + (8,128)
    want_vmem = 2 * (4096 + 8192 + 8192 + 4096) + 3 * 4096
    assert cost.vmem_bytes == want_vmem
    assert AP.kernel_vmem_bytes(eqns[0]) == want_vmem
    assert cost.smem_bytes == B * maxp * 4 + B * 4  # tables + lengths
    assert cost.double_buffered and cost.grid == (B, H, maxp)
    assert cost.vmem_bytes < AP.default_vmem_budget()
    assert cost.name == "_paged_kernel"


def test_flash_fwd_vmem_estimate_matches_linter_price():
    """kernels/flash_attention.fwd_vmem_bytes is the kernel's own
    statement of its working set — it must equal what the linter prices
    off the traced call (blocks + packed-lse plane + scratch; the SMEM
    klen vector excluded from both)."""
    from paddle_tpu.analysis import pallas as AP
    from paddle_tpu.kernels.flash_attention import (
        _plan_blocks, flash_attention, fwd_vmem_bytes,
        fwd_working_set_bytes)

    B, H_, S, D = 2, 2, 2048, 128
    qkv = jax.ShapeDtypeStruct((B, H_, S, D), jnp.bfloat16)
    eqns = _traced_pallas_eqns(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        force="interpret"), qkv, qkv, qkv)
    assert len(eqns) == 1
    priced = AP.kernel_vmem_bytes(eqns[0])
    # the blocks are the plan's, not a literal
    bq, bk = _plan_blocks(S, S, D, jnp.bfloat16, True, False)
    assert (bq, bk) != (128, 128)
    # the primal (inference) path drops the lse output entirely —
    # fwd_vmem_bytes(emit_lse=False) is its exact working set
    assert priced == fwd_vmem_bytes(
        block_q=bq, block_k=bk, head_dim=D, num_q_blocks=S // bq,
        dtype="bfloat16", emit_lse=False)
    # the training forward adds (only) the packed per-row lse plane
    bq, bk = _plan_blocks(S, S, D, jnp.bfloat16, True, True)
    with_lse = fwd_vmem_bytes(
        block_q=bq, block_k=bk, head_dim=D, num_q_blocks=S // bq,
        dtype="bfloat16", emit_lse=True)
    assert with_lse > priced
    # and what the plan holds under the budget counts the score blocks,
    # which no declared buffer shows the linter
    planned = fwd_working_set_bytes(bq, bk, D, S // bq, "bfloat16", True)
    assert planned >= with_lse + 2 * bq * bk * 4
    assert planned < AP.default_vmem_budget()


def test_corpus_vmem_overflow_exactly_its_detector_with_fields():
    """ISSUE acceptance: the VMEM-busting BlockSpec trips EXACTLY
    vmem-overflow, carries the per-finding vmem_bytes/budget fields
    into JSON, and the budget is configurable (a raised budget clears
    it)."""
    _skip_if_no_topology()
    from paddle_tpu import flags as fl

    art = build_corpus_program("vmem_overflow")
    findings = analysis.run_detectors(art)
    assert {f.detector for f in findings} == {"vmem-overflow"}
    f = findings[0]
    assert f.severity == "error"
    assert f.vmem_bytes > f.budget
    assert f.vmem_bytes == 2 * 2 * 4096 * 4096 * 4  # in+out, 2x buffered
    d = f.as_dict()
    assert d["vmem_bytes"] == f.vmem_bytes and d["budget"] == f.budget
    assert "vmem" in f.format()
    # the chip pipeline rejects the same program (RESOURCE_EXHAUSTED) —
    # the detector sees it BEFORE any compile, which is the point
    assert "vmem" in art.compile_error.lower()
    old = fl.flag("FLAGS_analysis_vmem_budget")
    fl.set_flags({"FLAGS_analysis_vmem_budget": 1 << 30})
    try:
        assert not [x for x in analysis.run_detectors(art)
                    if x.detector == "vmem-overflow"]
    finally:
        fl.set_flags({"FLAGS_analysis_vmem_budget": old})


def test_corpus_scan_widening_exactly_its_detector():
    """The bf16->f32 scan-carry escape trips EXACTLY scan-widening: the
    stacked fp32 history (2x the bf16 bytes) escapes to the program
    output; the small carry itself sits under the size floor."""
    _skip_if_no_topology()
    art = build_corpus_program("scan_widening")
    findings = analysis.run_detectors(art)
    assert {f.detector for f in findings} == {"scan-widening"}
    assert len(findings) == 1
    f = findings[0]
    assert "stacked output" in f.where
    assert f.bytes == 512 * 1024 * 4  # the [T, N] fp32 history
    assert f.severity == "warning"


def test_scan_widening_narrowed_accumulator_stays_clean():
    """The dtype-promotion contract carries over: a DELIBERATE fp32
    accumulator over bf16 rows that narrows back before the HBM write
    is the stats idiom, not a finding."""
    _skip_if_no_topology()
    from paddle_tpu.analysis.capture import capture_fn

    N = 1 << 19  # the f32 carry alone is 2 MB — above the floor

    def fn(x):  # [8, N] bf16
        def body(c, row):
            return c + row, ()

        c0 = jnp.zeros((N,), jnp.float32)
        c, _ = jax.lax.scan(body, c0, x)
        return c.astype(jnp.bfloat16)  # narrowed before the write

    art = capture_fn(fn, jax.ShapeDtypeStruct((8, N), jnp.bfloat16),
                     name="narrowed_accumulator")
    assert not [f for f in analysis.run_detectors(art)
                if f.detector == "scan-widening"]


def test_lint_inject_new_corpus_entries_exit_3(tmp_path, capsys):
    """Both new known-bad entries must fail `--inject <name> --gate`
    end-to-end (the ISSUE acceptance wording): scan_widening carries a
    finding, vmem_overflow additionally fails its AOT compile — exit 3
    either way."""
    _skip_if_no_topology()
    assert _lint_main(["--programs", "paged_decode",
                       "--inject", "scan_widening", "--gate"]) == 3
    assert _lint_main(["--programs", "paged_decode",
                       "--inject", "vmem_overflow", "--gate"]) == 3
    capsys.readouterr()


def test_sharded_decode_layout_tax_banked_at_zero():
    """ISSUE 14 acceptance: the banked sharded_decode entry holds
    relayout-copy-pair at ZERO (the oldest open finding count in the
    bank) — the kernel consumes XLA's preferred pool-shard layout
    (pool_layout="xla" + the kv_pool_layout program-boundary pin) — and
    the bytes/step win is banked (the taxed program priced 51.3 MB/chip
    per step; relayout-free must stay well under 45 MB)."""
    with open(analysis.default_baseline_path()) as f:
        progs = json.load(f)["programs"]
    entry = progs["sharded_decode"]
    assert entry["findings"].get("relayout-copy-pair", 0) == 0
    assert entry["findings"] == {}  # clean across ALL detectors
    assert entry["bytes_per_step"] < 45e6
    # every banked program is clean on the two new detectors (they are
    # gated from day one, the ROADMAP clause)
    for name, e in progs.items():
        assert e["findings"].get("vmem-overflow", 0) == 0, name
        assert e["findings"].get("scan-widening", 0) == 0, name


def test_findings_sorted_severity_then_bytes():
    """The one report order (stable gate diffs): strongest severity
    first, then biggest cost — vmem_bytes counts as the cost for
    non-traffic kernel findings."""
    from paddle_tpu.analysis import sort_findings

    fs = [
        Finding(detector="a", severity="warning", program="p",
                message="m", bytes=10),
        Finding(detector="b", severity="error", program="p",
                message="m", bytes=1),
        Finding(detector="c", severity="info", program="p",
                message="m", bytes=99),
        Finding(detector="d", severity="error", program="p",
                message="m", vmem_bytes=500, budget=100),
        Finding(detector="e", severity="warning", program="p",
                message="m", bytes=20),
    ]
    got = [f.detector for f in sort_findings(fs)]
    assert got == ["d", "b", "e", "a", "c"]


def test_scan_widening_catches_carry_aliased_with_dead_ys():
    """A body `return c, c` (the carry also emitted as a stacked output)
    whose caller keeps only the FINAL carry: the shared body var fills
    two outvar slots, and the carry slot must still be examined even
    though the ys slot is dead — a last-wins slot map would silently
    drop the exact hazard class the detector exists for."""
    _skip_if_no_topology()
    from paddle_tpu.analysis.capture import capture_fn

    N = 1 << 18  # the f32 carry alone is 1 MB — at the size floor

    def fn(x):  # [8, N] bf16
        def body(c, row):
            c = c + row  # widens: bf16 row joins the f32 carry
            return c, c  # carry AND stacked output are the same var

        c0 = jnp.zeros((N,))  # silently fp32
        c, _ = jax.lax.scan(body, c0, x)
        return c  # only the widened final carry escapes

    art = capture_fn(fn, jax.ShapeDtypeStruct((8, N), jnp.bfloat16),
                     name="carry_aliased_ys")
    hit = [f for f in analysis.run_detectors(art)
           if f.detector == "scan-widening"]
    assert hit and any(f.where == "scan carry 0" for f in hit)
