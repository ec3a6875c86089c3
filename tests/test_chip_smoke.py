"""chip_smoke.py rehearsed on the CPU mesh, and the plumbing it stands on.

The smoke itself only runs on a TPU (``main()`` must refuse anything
else before it builds a model); its leg functions are ordinary functions
of their sizes, so the suite runs them at shapes it already compiles
(ResNet-18 at 32x32, BERT-tiny, the 2-layer GPT of the serving tests) and
checks they emit the fields the chip run prints.  Also here: the compile
cache helper and the device-plumbing refusals
(kernel import failure on TPU, local children on an accelerator, a failed
native build).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

LEG_FIELDS = {"leg", "compile_s", "traces_after_warmup", "compiled",
              "attention_paths", "peak_bytes", "bytes_in_use"}
TRAIN_FIELDS = LEG_FIELDS | {
    "steps_done", "first_loss", "last_loss", "found_inf_steps",
    "loss_scale", "smoke_step_ms", "ddp_comm_bytes_per_step",
    "replicas_identical"}
ENGINE_FIELDS = LEG_FIELDS | {
    "requests_done", "tokens_produced", "smoke_window_ms", "window",
    "blocks_free", "blocks_total", "midwindow_admissions",
    "matches_generate_cached", "first_divergence",
    "reference_attention_paths"}


def test_main_refuses_cpu_before_building_anything(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "legs",
                        lambda ndev: pytest.fail("legs were built"))
    assert chip_smoke.main() not in (0, None)
    out, err = capsys.readouterr()
    assert out == ""                       # no result line without a chip
    assert "'cpu'" in err and "TPU" in err


@pytest.mark.parametrize("var", ["APEX_TPU_DISABLE_PALLAS",
                                 "APEX_TPU_FORCE_PALLAS"])
def test_main_refuses_dispatch_switches(monkeypatch, capsys, var):
    monkeypatch.setenv(var, "1")
    assert chip_smoke.main() == 2
    assert var in capsys.readouterr().err


def test_resnet_leg_at_tiny_size():
    rec = chip_smoke.train_leg(
        name="resnet18_tiny", example="examples/imagenet/main_amp.py",
        argv=["--arch", "resnet18", "-b", "2", "--image-size", "32"],
        steps=2)
    assert TRAIN_FIELDS <= set(rec)
    assert rec["steps_done"] == 2 and len(rec["smoke_step_ms"]) == 2
    assert rec["last_loss"] < rec["first_loss"]      # same batch each step
    assert rec["traces_after_warmup"] == 0
    assert rec["replicas_identical"] is True
    assert rec["ddp_comm_bytes_per_step"] > 0
    assert rec["compiled"]["planned_peak_bytes"] > 0
    json.dumps(rec)


def test_bert_leg_at_tiny_size_and_a_failed_check():
    """One run covers both: on the CPU attention goes dense, so asking
    for flash must fail the leg — with everything it measured attached."""
    with pytest.raises(chip_smoke.LegFailed, match="dense") as ei:
        chip_smoke.train_leg(
            name="bert_tiny", example="examples/bert/main_amp.py",
            argv=["--config", "tiny", "-b", "2", "--seq-len", "16"],
            steps=2, expect_attention="flash")
    rec = ei.value.rec
    assert TRAIN_FIELDS <= set(rec)
    assert rec["attention_paths"] == ["dense"]
    assert rec["traces_after_warmup"] == 0
    assert rec["loss_scale"] >= 1.0 and rec["found_inf_steps"] == 0


def test_engine_leg_at_tiny_size():
    from apex_tpu import models
    rec = chip_smoke.engine_leg(
        name="gpt_tiny_paged",
        cfg=models.GPTConfig(vocab_size=128, block_size=32, n_layer=2,
                             n_head=4, n_embd=32, dropout=0.0),
        slots=3, buf_len=32, block_size=8, window=4, requests=5,
        prompt_len=6, new_tokens=5)
    assert ENGINE_FIELDS <= set(rec)
    assert rec["requests_done"] == 5 and rec["tokens_produced"] == 25
    # bf16 near-ties may flip a greedy token; every request either
    # matches the reference or says where and how narrowly it parted
    assert (rec["matches_generate_cached"] + len(rec["first_divergence"])
            == 5)
    assert all("position" in d for d in rec["first_divergence"].values())
    assert rec["blocks_free"] == rec["blocks_total"]
    assert rec["midwindow_admissions"] >= 1     # 5 requests, 3 slots
    assert rec["traces_after_warmup"] == 0
    json.dumps(rec)


def test_a_failed_leg_is_named_and_does_not_hide_the_next(capsys):
    def fails(name):
        raise chip_smoke.LegFailed("loss not finite", {"leg": name, "x": 1})

    def crashes(name):
        raise RuntimeError("mosaic said no")

    def passes(name):
        return {"leg": name}

    failed = chip_smoke.run_legs(
        [(fails, {"name": "a"}), (crashes, {"name": "b"}),
         (passes, {"name": "c"})], {"platform": "tpu"})
    assert failed == ["a", "b"]
    out, err = capsys.readouterr()
    lines = [json.loads(ln) for ln in out.splitlines()]
    assert [(ln["leg"], ln["ok"]) for ln in lines] == [
        ("a", False), ("b", False), ("c", True)]
    assert lines[0]["x"] == 1 and "mosaic said no" in lines[1]["error"]
    assert all(ln["platform"] == "tpu" and ln["cache_hits"] == 0
               and ln["cache_misses"] == 0 for ln in lines)
    assert "leg a FAILED" in err and "leg b FAILED" in err


# -- compile cache helper ---------------------------------------------------

@pytest.fixture
def config_updates(monkeypatch):
    import jax
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    return calls


def test_cache_helper_sets_nothing_when_placed_from_outside(
        monkeypatch, config_updates):
    from apex_tpu.utils import configure_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert configure_compile_cache() == "/some/dir"
    assert config_updates == []


def test_cache_helper_uses_the_fixed_in_checkout_path(
        monkeypatch, config_updates):
    from apex_tpu.utils import configure_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_compile_cache")
    assert configure_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)]


def test_only_the_helper_places_the_cache():
    """No other file sets ``jax_compilation_cache_dir`` (the in-process
    save-and-restore of tests/test_compilation.py aside)."""
    r = subprocess.run(
        ["git", "grep", "-l", "-e", "jax_compilation_cache_dir", "--",
         "*.py"], cwd=ROOT, capture_output=True, text=True)
    if r.returncode not in (0, 1):
        pytest.skip("not a git checkout")
    assert set(r.stdout.split()) <= {
        "apex_tpu/utils/compile_cache.py", "tests/test_compilation.py",
        "tests/test_chip_smoke.py"}


# -- device plumbing that must not hide the device ---------------------------

def test_kernel_import_failure_raises_on_tpu_only(monkeypatch):
    import apex_tpu.ops as ops
    from apex_tpu.ops import dispatch
    import apex_tpu.ops.pallas_adam  # noqa: F401 — make sure it was loaded
    monkeypatch.delattr(ops, "pallas_adam")
    monkeypatch.setitem(sys.modules, "apex_tpu.ops.pallas_adam", None)
    monkeypatch.setattr(dispatch, "_KERNELS_AVAILABLE", None)
    assert dispatch.kernels_available() is False        # CPU: jnp path
    monkeypatch.setattr(dispatch, "_KERNELS_AVAILABLE", None)
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    with pytest.raises(ImportError):
        dispatch.kernels_available()


def test_multiproc_refuses_local_children_on_an_accelerator(
        monkeypatch, capsys):
    from apex_tpu.parallel import multiproc
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(subprocess, "Popen",
                        lambda *a, **kw: pytest.fail("spawned a child"))
    assert multiproc.main(["--nprocs", "2", "script.py"]) == 2
    assert "refusing" in capsys.readouterr().err


@pytest.mark.skipif(shutil.which("g++") is None, reason="no compiler: the "
                    "library is absent and no build is attempted")
def test_native_failed_build_says_so_once(monkeypatch, capsys, tmp_path):
    from apex_tpu import _native
    (tmp_path / "build.sh").write_text(
        "echo 'apex_tpu_C.cpp:1: error: nope' >&2; exit 3\n")
    monkeypatch.setattr(_native, "_HERE", str(tmp_path))
    monkeypatch.setattr(_native, "_SO", str(tmp_path / "lib.so"))
    monkeypatch.setattr(_native, "_lib", None)
    monkeypatch.setattr(_native, "_load_failed", False)
    assert _native.available() is False
    assert _native.available() is False
    err = capsys.readouterr().err
    assert err.count("apex_tpu._native:") == 1
    assert "build.sh exited 3" in err and "error: nope" in err
