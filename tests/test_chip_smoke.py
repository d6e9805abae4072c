"""chip_smoke.py off the chip: it refuses a CPU backend, and its phases
run end to end at a tiny size (reduced smollm, interpret-mode kernels)."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


@pytest.fixture
def cache_dir(monkeypatch, tmp_path):
    import jax

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_refuses_cpu(capsys, cache_dir):
    assert chip_smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "not 'tpu'" in err


def test_phases_at_tiny_size(monkeypatch, capsys, cache_dir):
    import jax

    from repro import configs
    from repro.core import encoding as E

    cfg = configs.smoke_config("smollm-360m")
    monkeypatch.setattr(configs, "get_arch", lambda name: cfg)
    monkeypatch.setattr(chip_smoke, "VOCAB", cfg.vocab_size)
    monkeypatch.setattr(chip_smoke, "MIN_TOKENS", 100_000)
    monkeypatch.setattr(chip_smoke, "SEQ", 64)
    monkeypatch.setattr(chip_smoke, "STEPS", 2)
    # what the chip decides, decided here: the kernels run (interpret
    # mode on CPU) and the device check reports the CPU
    monkeypatch.setattr(chip_smoke, "decode_kernels_lowered", lambda: 1)
    monkeypatch.setattr(E.OFFSETS_SCAN, "backend", "pallas")
    monkeypatch.setattr(E.BYTESHUFFLE, "backend", "pallas")
    d = jax.devices()[0]
    monkeypatch.setattr(chip_smoke, "phase_device", lambda want: {
        "platform": d.platform, "kind": d.device_kind, "count": 1})

    assert chip_smoke.main(["--seed", "3"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    phases = [ln.split("]")[0][1:] for ln in lines if ln.startswith("[")]
    assert phases == ["device", "ingest", "ingest", "decode", "train",
                      "restore"]
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": d.platform, "kind": d.device_kind,
                   "count": 1}}
    restore = next(ln for ln in lines if ln.startswith("[restore]"))
    assert "bit-identical" in restore
