"""chip_smoke.py off the chip: what the CPU can still say about it.

- every Pallas kernel LOWERS for the TPU (Mosaic lowering runs on the
  CPU; compiling does not) at the shapes chip_smoke.py compiles them at —
  the check that stops a kernel PR from shipping a body only the
  interpreter accepts;
- without an accelerator the script exits non-zero and prints no result;
  a phase that raises makes the run fail;
- (slow) the phases rehearse end to end at tiny sizes on the virtual
  8-device mesh with the kernel bodies in interpret mode. A rehearsal is
  never a pass: it keeps the script's own plumbing from rotting between
  chip runs.
"""
import json
import os
import subprocess
import sys

import numpy as onp
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from mxnet_tpu.ops import kernels  # noqa: E402

CASES = chip_smoke.kernel_cases()


class _ZeroRng:
    """RandomState's shapes without its work: lowering needs avals, and
    the largest case is 50M elements."""

    def standard_normal(self, shape):
        return onp.zeros(shape)

    def rand(self, *shape):
        return onp.zeros(shape)

    def randint(self, low, high, size):
        return onp.full(size, low)


def test_cases_cover_every_kernel_in_both_dtypes():
    assert {c.kernel for c in CASES} == set(kernels.KERNELS)
    for name in set(kernels.KERNELS) - {"opt_update"}:     # flat f32 shards
        assert {c.dtype for c in CASES if c.kernel == name} == \
            {"bfloat16", "float32"}


@pytest.mark.parametrize("case", CASES, ids=[c.label for c in CASES])
def test_kernel_lowers_for_tpu(case, monkeypatch):
    """Forward and backward through Mosaic lowering at the source-cell
    shape. The dispatch gate is told the backend is a TPU, so the public
    entry point picks the compiled (not interpreted) kernel, exactly as
    it will on the chip."""
    monkeypatch.delenv("MXNET_PALLAS", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype)
            for a in chip_smoke.case_args(case, _ZeroRng())]
    cots = [jax.ShapeDtypeStruct(o.shape, "float32") for o in
            jax.eval_shape(chip_smoke.kernel_outputs(case), *args)]
    jax.jit(chip_smoke.kernel_program(case)).trace(cots, *args).lower(
        lowering_platforms=("tpu",))
    assert kernels.decisions()[case.kernel][0] == "pallas"


def test_no_accelerator_exits_nonzero_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        timeout=120)
    assert proc.returncode not in (0, None)
    assert proc.stdout == b""
    assert b"no TPU" in proc.stderr


def test_a_phase_that_raises_fails_the_run(capsys):
    def planted(_):
        raise RuntimeError("planted")

    report = chip_smoke.run_phases({
        "fine": lambda _: {"n": 1},
        "broken": planted,
        "partly": lambda _: {"failed": ["one"]},
        "later": lambda r: {"skipped": r["fine"]["n"]}})
    assert [p["status"] for p in report.values()] == \
        ["ok", "failed", "failed", "skipped"]
    assert "RuntimeError: planted" in capsys.readouterr().out


def test_last_line_is_the_verdict_and_nothing_else(capsys):
    """The driver reads the LAST stdout line and takes exactly ``ok`` and
    ``device`` {platform, kind, count}; the report is the line before."""
    from collections import namedtuple
    dev = namedtuple("dev", "platform device_kind")("tpu", "TPU v5 lite")
    cache = {"dir": "/x", "hits": 3, "misses": 0, "requests": 3}
    phases = {"device": {"status": "ok", "seconds": 0.0},
              "dp": {"status": "skipped", "seconds": 0.0}}
    assert chip_smoke.finish(phases, [dev], cache, True) == 0
    report, verdict = capsys.readouterr().out.splitlines()
    assert json.loads(verdict) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert report.endswith('"claim": null}')
    assert json.loads(report)["compile_cache"] == {
        "dir": "/x", "hits": 3, "misses": 0}

    phases["serve"] = {"status": "failed", "seconds": 1.0}
    assert chip_smoke.finish(phases, [dev] * 4, cache, False) == 1
    verdict = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert verdict == {"ok": False, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


def test_hybrid_phase_is_listed_and_its_dry_path_runs(monkeypatch):
    """The phase a run makes between ``kernels`` and ``serve``, at a tiny
    size on the CPU with the kernel bodies in interpret mode: a small
    ``NemotronHLM``, the chunked scan against the recurrence, the
    counters."""
    assert list(chip_smoke.phases()) == ["device", "train", "dp", "kernels",
                                         "hybrid", "serve"]
    monkeypatch.setenv("MXNET_PALLAS", "on")
    # heads of 64 lanes over a state of 128 in chunks of 128, as the
    # phase's own: shapes the scan's kernels take
    cfg = dict(chip_smoke.HYBRID, batch=2, seq=160, steps=4,
               scan=dict(seq=300, heads=4, groups=2), model=dict(
                   chip_smoke.HYBRID["model"], hidden_size=128,
                   mamba_num_heads=4, head_dim=32, moe_intermediate_size=128,
                   moe_shared_expert_intermediate_size=128, vocab_size=128))
    out = chip_smoke.phase_hybrid(cfg)
    assert set(out) == {"scan_gap", "lm"}
    assert out["scan_gap"]["float32"] < 1e-5 < out["scan_gap"]["bfloat16"]
    got = out["lm"]
    assert got["loss"][-1] < got["loss"][0]
    assert got["mx_ssd_scan_chunks_total"] == 2 * 2
    assert got["mx_ssd_scan_total"] == {"interpret": 2}
    assert got["mx_mamba_recompute_total"] == {"segment": 2}
    assert got["mx_moe_router_total"] == {"sigmoid": 2}
    # widths of 128 and a list of 128 rows: the kernels' bodies, two
    # products forward and three backward an expert layer
    assert set(got["mx_moe_grouped_dot_total"]) == {"interpret"}
    assert got["mx_moe_grouped_dot_total"]["interpret"] >= 10
    json.dumps(out)


@pytest.mark.slow
def test_phases_rehearse_on_cpu(monkeypatch):
    """train -> dp (8 virtual devices) -> kernels -> serve at tiny sizes,
    kernel bodies in interpret mode."""
    monkeypatch.setenv("MXNET_PALLAS", "on")
    with pytest.raises(RuntimeError, match="not one the program knows"):
        chip_smoke.phase_device()
    cfg = dict(chip_smoke.TRAIN, batch=8, seq=32, steps=4, vocab=128,
               bert="bert_small_test",
               optimizer=("adam", {"learning_rate": 1e-3}))
    sparse = dict(chip_smoke.SPARSE, batch=2, seq=32, steps=4, model=dict(
        chip_smoke.SPARSE["model"], hidden_size=128, head_dim=32,
        moe_ffn_hidden_size=128, sliding_window_size=8, vocab_size=128))
    latent = dict(chip_smoke.LATENT, batch=2, seq=32, steps=4, model=dict(
        chip_smoke.LATENT["model"], hidden_size=128, intermediate_size=64,
        q_lora_rank=64, kv_lora_rank=32, moe_intermediate_size=128,
        vocab_size=128))
    train = chip_smoke.phase_train(cfg, sparse, latent)
    assert train["kernel_paths"]["flash_attention"] == "interpret"
    assert train["sparse_lm"]["mx_moe_dispatch_total"] == {"grouped": 4}
    assert train["sparse_lm"]["mx_attention_mask_total"] == {
        "causal": 1, "window": 3}
    # 2 x 32 tokens of 128, top-2: a list of 128 rows, which the row
    # movers' kernels take
    movers = train["sparse_lm"]["mx_moe_row_mover_total"]
    assert set(movers) == {"interpret"} and movers["interpret"] >= 12
    # and the embedding table's gradient (128 rows of 128, 64 ids)
    assert set(train["sparse_lm"]["mx_embedding_grad_total"]) == {
        "interpret"}
    # and so do the grouped products' (widths of 128): eight a layer
    products = train["sparse_lm"]["mx_moe_grouped_dot_total"]
    assert set(products) == {"interpret"} and products["interpret"] >= 32
    assert set(train["latent_lm"]["mx_moe_grouped_dot_total"]) == {
        "interpret"}
    # 32 positions in blocks of 32: a step a program, none dead, BERT's
    # backward one block; the sparse LM's grouped heads take the fused
    # backward, one kernel a layer
    assert train["flash_grid_steps"]["dead"] == 0 < \
        train["flash_grid_steps"]["live"]
    forms = train["flash_bwd_forms"]
    assert forms["one_block"] > 0 and forms["fused"] == forms["split"] == 0
    sparse_steps = train["sparse_lm"]["mx_flash_attention_grid_steps_total"]
    assert sparse_steps["dead"] == 0 and sparse_steps["live"] % 4 == 0
    sparse_forms = train["sparse_lm"]["mx_flash_attention_bwd_total"]
    assert sparse_forms["split"] == 0 and sparse_forms["fused"] >= 4
    # the small JoyAILM: one dense and one expert layer and the MTP
    # module's, each behind latent attention with keys of 192 lanes beside
    # values of 128, which the kernels take where they lie
    assert train["latent_lm"]["mx_latent_attention_total"] == {"expanded": 3}
    assert train["latent_lm"]["mx_moe_router_total"] == {"sigmoid": 2}
    assert train["latent_lm"]["flash_layouts"] == {
        "packed": 3, "unpadded": 0, "padded": 0}
    assert train["latent_lm"]["loss"][-1] < train["latent_lm"]["loss"][0]
    dp = chip_smoke.phase_dp(train["loss"], cfg)
    assert dp["devices"] == 8 and dp["collectives"]["all-gather"]
    checked = chip_smoke.phase_kernels(tiny=True)
    assert checked["failed"] == []
    assert checked["kernel_paths"] == dict.fromkeys(kernels.KERNELS,
                                                    "interpret")
    serve = chip_smoke.phase_serve(dict(
        chip_smoke.SERVE, vocab=64, d_model=32, heads=2, requests=6,
        ladder=(1, 2, 4), page_size=8))
    assert set(serve) == {"TinyDecoder", "GQADecoder"}
    json.dumps([train, dp, checked, serve])
