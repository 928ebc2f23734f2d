"""Speculative decoding at the tool and in the bank (ISSUE 13, ISSUE 16):
the half of tests/test_speculative.py's contract that runs
tools/serve_bench.py and reads the banked AOT numbers, in a file of its own
since PR 54 (a file is one worker's under `--dist loadfile`, and the two
halves together were the longest file of tier-1).

(f) serve_bench --speculate/--sampling scenarios on the 0/2/3 gate
    contract (usage errors exit 2) with acceptance_rate > 0 and
    tokens/s above the same invocation's d=0 arm — ISSUE 16 extends
    the matrix with sampled (topk), --mesh, and corpus-drafted
    --prefix-share speculation arms;
(g) the spec_verify zoo entry is banked under require_all coverage at
    < 2x the d=0 gqa_decode bytes/step, and the known-bad
    spec_verify_gather corpus arm trips the bytes gate; the SPMD
    mirror (spec_verify_spmd / spec_verify_spmd_gather) holds the
    same contract for the mesh verify step.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------------
# (f) serve_bench scenarios + gate contract


def _bench_main(argv):
    sys.path.insert(0, os.path.abspath(REPO))
    try:
        from tools.serve_bench import main

        return main(argv)
    finally:
        sys.path.pop(0)


def test_serve_bench_speculate_smoke_and_gate(tmp_path, capsys):
    rc = _bench_main([
        "--mode", "decode", "--sequences", "4", "--max-new", "8",
        "--speculate", "4", "--prompt-range", "6,12", "--pages", "64",
        "--json", str(tmp_path / "out.json")])
    assert rc == 0
    out = json.loads((tmp_path / "out.json").read_text())
    capsys.readouterr()
    assert out["speculate"] == 4 and out["sampling"] == "greedy"
    assert out["acceptance_rate"] > 0
    assert out["drafted_tokens"] >= out["accepted_tokens"] > 0
    # the headline the CPU can hold: more than one token a step (a count;
    # tokens/s of one arm over the other is the chip's to say)
    assert out["tokens_per_step"] > 1.0
    assert out["pages_leaked"] == 0
    # bank it and re-gate: the win is now held by CI
    bank = {k: out[k] for k in ("acceptance_rate", "tokens_per_step",
                                "pages_leaked")}
    bank_path = tmp_path / "SPEC_BANK.json"
    bank_path.write_text(json.dumps(bank))
    assert _bench_main([
        "--mode", "decode", "--sequences", "4", "--max-new", "8",
        "--speculate", "4", "--prompt-range", "6,12", "--pages", "64",
        "--baseline", str(bank_path), "--tol", "0.5", "--gate"]) == 0
    capsys.readouterr()


def test_serve_bench_speculate_gate_refuses_an_unreachable_bank(tmp_path,
                                                                capsys):
    """The gate's teeth, on a count: a bank that asks for more tokens a
    step than a block of 4 drafts can hold must exit 3."""
    bank_path = tmp_path / "SPEC_BANK.json"
    bank_path.write_text(json.dumps({"tokens_per_step": 99.0}))
    assert _bench_main([
        "--mode", "decode", "--sequences", "4", "--max-new", "8",
        "--speculate", "4", "--prompt-range", "6,12", "--pages", "64",
        "--baseline", str(bank_path), "--gate"]) == 3
    capsys.readouterr()


def test_serve_bench_sampled_speculation_smoke(tmp_path, capsys):
    """ISSUE 16: --speculate composes with a non-greedy --sampling —
    the exit-2 refusal is gone, rollbacks occur, nothing leaks, and
    the d=0 comparison arm still runs (the in-process replay-identity
    check already passed or the run would have exited 2)."""
    rc = _bench_main([
        "--mode", "decode", "--sequences", "4", "--max-new", "8",
        "--speculate", "3", "--sampling", "topk", "--pages", "96",
        "--page-size", "8", "--max-len", "96",
        "--json", str(tmp_path / "out.json")])
    capsys.readouterr()
    assert rc == 0
    out = json.loads((tmp_path / "out.json").read_text())
    assert out["sampling"] == "topk" and out["speculate"] == 3
    assert out["acceptance_rate"] > 0
    assert out["rolled_back_tokens"] > 0   # the epilogue rejected
    assert out["pages_leaked"] == 0
    assert out["spec_speedup"] > 0 and out["tokens_per_s_d0"] > 0


def test_serve_bench_mesh_speculation_smoke(tmp_path, capsys):
    """--speculate composes with --mesh: the SPMD program's multi-token
    verify runs the draft blocks and the d=0 arm compares mesh against
    mesh (greedy, so the token-identity check held in-process)."""
    rc = _bench_main([
        "--mode", "decode", "--sequences", "4", "--max-new", "10",
        "--mesh", "2", "--speculate", "2", "--pages", "64",
        "--page-size", "4", "--max-len", "48",
        "--json", str(tmp_path / "out.json")])
    capsys.readouterr()
    assert rc == 0
    out = json.loads((tmp_path / "out.json").read_text())
    assert out["mesh"] == 2 and out["speculate"] == 2
    assert out["acceptance_rate"] > 0
    assert out["pages_leaked"] == 0
    assert out["tokens_per_s_d0"] > 0


def test_serve_bench_corpus_drafted_prefix_share_smoke(tmp_path,
                                                      capsys):
    """Shared-prefix traffic drafts from the prefix cache's corpus: the
    acceptance rate on a --prefix-share arm sits far above what own-
    history lookup alone reaches on random prompts."""
    rc = _bench_main([
        "--mode", "decode", "--sequences", "6", "--max-new", "12",
        "--speculate", "3", "--prefix-share", "0.6", "--pages", "128",
        "--page-size", "8", "--max-len", "96",
        "--json", str(tmp_path / "out.json")])
    capsys.readouterr()
    assert rc == 0
    out = json.loads((tmp_path / "out.json").read_text())
    assert out["prefix_hit_rate"] > 0
    assert out["acceptance_rate"] > 0.5   # corpus-fed drafts land
    assert out["pages_leaked"] == 0


def test_serve_bench_sampling_scenario_smoke(tmp_path, capsys):
    rc = _bench_main([
        "--mode", "decode", "--sequences", "4", "--max-new", "8",
        "--sampling", "topp", "--json", str(tmp_path / "out.json")])
    capsys.readouterr()
    assert rc == 0
    out = json.loads((tmp_path / "out.json").read_text())
    assert out["sampling"] == "topp" and out["pages_leaked"] == 0


def test_serve_bench_speculate_usage_errors_exit_2(capsys):
    cases = [
        ["--mode", "engine", "--speculate", "2"],
        ["--mode", "decode", "--speculate", "-1"],
        ["--mode", "decode", "--speculate", "2", "--chaos"],
        ["--mode", "engine", "--sampling", "topk"],
    ]
    for argv in cases:
        assert _bench_main(argv) == 2, argv
        capsys.readouterr()


# ---------------------------------------------------------------------------
# (g) the banked zoo entry + known-bad corpus arm


def test_spec_verify_banked_under_2x_gqa_decode_with_coverage():
    from paddle_tpu import analysis

    with open(analysis.default_baseline_path()) as f:
        progs = json.load(f)["programs"]
    assert "spec_verify" in progs  # require_all coverage from here on
    spec = progs["spec_verify"]["bytes_per_step"]
    gqa = progs["gqa_decode"]["bytes_per_step"]
    assert spec < 2 * gqa, (spec, gqa)
    q_tokens = progs["spec_verify"]["config"]["q_tokens"]
    assert q_tokens == 5  # d = 4
    # >= 2x effective bytes-per-token reduction at full acceptance
    assert gqa / (spec / q_tokens) >= 2.0
    assert progs["spec_verify"]["findings"] == {}


def test_spec_verify_gather_corpus_trips_bytes_gate():
    """The known-bad arm: a verify step re-materializing the full
    [B,H,S,D] gather prices far above the banked page stream — the
    bytes gate (not a detector) is its teeth, end to end through
    lint_programs --inject ... --gate exiting 3."""
    from paddle_tpu import analysis
    from paddle_tpu.analysis.corpus import build_corpus_program

    pytest.importorskip("jax")
    art = build_corpus_program("spec_verify_gather")
    if art.compile_error:
        pytest.skip(f"AOT topology unavailable: {art.compile_error}")
    assert art.name == "spec_verify"  # deliberately the zoo entry's slot
    bad = analysis.ZooResult(
        name=art.name, artifacts=art, findings=[],
        bytes_per_step=art.bytes_per_step, flops_per_step=0.0)
    verdicts, failed = analysis.gate(
        [bad], analysis.default_baseline_path())
    assert failed
    v = [x for x in verdicts
         if x["metric"] == "spec_verify_aot_bytes_per_step"]
    assert v and v[0]["verdict"] == "fail"


def test_spec_verify_spmd_banked_under_require_all():
    """The mesh mirror of the spec_verify entry: the SPMD multi-token
    verify step is banked (require_all coverage — dropping it fails
    the lint gate) at the same q_tokens = 1 + d width, findings
    clean, on the 4-shard v5e topology."""
    from paddle_tpu import analysis

    with open(analysis.default_baseline_path()) as f:
        progs = json.load(f)["programs"]
    assert "spec_verify_spmd" in progs
    e = progs["spec_verify_spmd"]
    assert e["config"]["q_tokens"] == 5       # d = 4, Sq = 1 + d
    assert e["config"]["n_shards"] == 4
    assert e["config"]["impl"] == "pallas"
    assert e["findings"] == {}
    assert e["bytes_per_step"] > 0 and e["flops_per_step"] > 0


def test_spec_verify_spmd_gather_corpus_trips_bytes_gate():
    """The known-bad mesh arm: swapping the verify step's paged kernel
    for the reference gather re-materializes [B, H, S, D] per chip —
    at the banked 1024-token context that prices above the tolerance
    band and the bytes gate fails it in spec_verify_spmd's slot."""
    from paddle_tpu import analysis
    from paddle_tpu.analysis.corpus import build_corpus_program

    pytest.importorskip("jax")
    art = build_corpus_program("spec_verify_spmd_gather")
    if art.compile_error:
        pytest.skip(f"AOT topology unavailable: {art.compile_error}")
    assert art.name == "spec_verify_spmd"  # the zoo entry's slot
    bad = analysis.ZooResult(
        name=art.name, artifacts=art, findings=[],
        bytes_per_step=art.bytes_per_step, flops_per_step=0.0)
    verdicts, failed = analysis.gate(
        [bad], analysis.default_baseline_path())
    assert failed
    v = [x for x in verdicts
         if x["metric"] == "spec_verify_spmd_aot_bytes_per_step"]
    assert v and v[0]["verdict"] == "fail"
