"""``chip_smoke.py`` off the chip: it must fail there, and each of its
phases is rehearsed at a tiny size so that a chip call is not spent on
a wrong path, argument or sharding rule.  The sizes and the platform
are steered here, in the test; the program has no option for either.
"""

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import pytest  # noqa: E402

import chip_smoke  # noqa: E402


@pytest.fixture
def no_cache_dir(monkeypatch):
    """The phases run in this process here: keep them from pointing
    the rest of the session's compiles at the persistent cache."""
    monkeypatch.setattr("horovod_tpu.common.compile_cache.enable",
                        lambda: None)


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep +
               os.environ.get("PYTHONPATH", ""), **extra)
    env.pop("XLA_FLAGS", None)
    env.pop("HOROVOD_RANK", None)
    return env


def _launch(tmp_path, np, body, **extra):
    """Run ``body`` (a chip_smoke phase call) under the launcher, as
    chip_smoke's parent runs its phases, and return its result lines."""
    script = tmp_path / "phase.py"
    script.write_text("import chip_smoke\n" + textwrap.dedent(body))
    out = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner.launch", "-np",
         str(np), sys.executable, str(script)], env=_env(**extra),
        check=True, capture_output=True, text=True, timeout=240).stdout
    return [json.loads(line.split(chip_smoke.RESULT_MARK, 1)[1])
            for line in out.splitlines() if chip_smoke.RESULT_MARK in line]


def test_chip_smoke_fails_without_a_tpu():
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=_env(), capture_output=True, text=True,
                       timeout=240)
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"] is None
    assert last["failed"] == ["flash", "trainer"]
    assert '"ok": true' not in r.stdout


def test_rehearse_flash_phase(capsys, no_cache_dir):
    chip_smoke.phase_flash(
        cases=(((2, 64, 2, 16), False), ((1, 96, 2, 16), True)),
        platform="cpu", interpret=True)
    assert chip_smoke.RESULT_MARK + '{"phase": "flash", "ok": true' \
        in capsys.readouterr().out


def test_rehearse_trainer_phase_under_the_launcher(tmp_path):
    results = _launch(tmp_path, 1, """
        from horovod_tpu.models.bert import bert_tiny_config
        chip_smoke.phase_trainer(
            config=bert_tiny_config(hidden_dropout=0.1,
                                    attention_dropout=0.1),
            batch_size=8, seq_len=32, steps=5, platform="cpu")
    """)
    assert [(r["phase"], r["ok"], r["steps"]) for r in results] == \
        [("trainer", True, 5)]


def test_rehearse_sharded_phase_on_four_devices(capsys, no_cache_dir):
    from horovod_tpu.models.bert import bert_tiny_config
    from horovod_tpu.models.gpt import gpt_tiny_config
    chip_smoke.phase_sharded(
        bert_config=bert_tiny_config(num_layers=1),
        gpt_config=gpt_tiny_config(num_layers=1),
        bert_batch=(8, 32), gpt_batch=(8, 32),
        gpt_check_config=gpt_tiny_config(remat=True),
        gpt_check_batch=(2, 40), platform="cpu")
    out = capsys.readouterr().out
    assert "against the reference," in out and "against the logits path," in out
    assert out.count("a tensor-parallel weight in two distinct shards") == 2
    assert chip_smoke.RESULT_MARK + '{"phase": "sharded", "ok": true' in out


def test_rehearse_eager_phase_four_processes(tmp_path):
    """Four ranks, one device each, on the XLA mesh plane the TPU
    uses (the CPU default would be the ring)."""
    results = _launch(tmp_path, 4, """
        chip_smoke.phase_eager(platform="cpu")
    """, HOROVOD_CPU_OPERATIONS="XLA")
    assert len(results) == 4
    assert all(r["phase"] == "eager" and r["ok"] and r["ranks"] == 4 and
               r["device"]["count"] == 4 for r in results)
