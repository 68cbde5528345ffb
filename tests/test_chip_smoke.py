"""chip_smoke.py off the chip: its serve phase at reduced(granite-3-2b)
on the CPU, its refusal to report success without a TPU, and its tp=4
comparison on four virtual devices."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax

from conftest import REPO, run_devices

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402
from repro.configs import get_config, reduced  # noqa: E402


def test_serve_phase_at_reduced_width():
    """The smoke's geometry (batch 8, max_len 2048, pages of 16, chunk
    256, 8 requests of 128-1024 prompt tokens, 32 greedy new tokens) on
    a toy-width granite: every request served in full, logits finite."""
    cfg = reduced(get_config(chip_smoke.ARCH))
    facts, _ = chip_smoke.serve(cfg, chip_smoke._pcfg(1),
                                chip_smoke._mesh(jax.devices()[:1]))
    assert facts["served_ok"], facts
    assert facts["served"] == 8 and facts["finite"]
    assert all(len(t) == 32 for t in facts["tokens"])
    assert facts["steps_prefill"] > 0 and facts["steps_decode"] >= 32


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok": true' not in out
    assert not out.strip().splitlines()[-1].startswith("{")


def test_tp4_serving_matches_tp1_on_virtual_devices():
    """tp_relayout gives the tp=4 program the tp=1 weights: both serve
    the same model, and the tp=4 weights and pools span all devices."""
    out = run_devices(textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        from repro.configs import get_config, reduced
        # 255 does not split over 4 ranks: the vocab padding is relaid too
        cfg = reduced(get_config("granite-3-2b"), num_heads=8,
                      num_kv_heads=4, vocab_size=255)
        ok = chip_smoke.tp_compare(cfg, 0, prompt_lens=(16, 96),
                                   new_tokens=8, max_len=256, chunk=32)
        print(json.dumps({{"ok": ok}}))
    """), devices=4)
    assert "tp=4 params and pools span 4 devices: True" in out
    assert json.loads(out.strip().splitlines()[-1]) == {"ok": True}


def test_smoke_alone_fails(tmp_path):
    """Without the checkout beside it, the script exits non-zero and
    prints no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120,
                          env={k: v for k, v in os.environ.items()
                               if k != "PYTHONPATH"})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
