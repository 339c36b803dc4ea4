"""chip_smoke.py refuses to stand in for a chip run: without a TPU, or
with the kernel dispatch forced, it exits non-zero and prints no result."""
from __future__ import annotations

import importlib.util
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("dequant_impl", ["", "pallas"])
def test_chip_smoke_refuses_without_a_tpu(monkeypatch, capsys,
                                          dequant_impl):
    import jax

    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached: chip_smoke.py would run")
    monkeypatch.setenv("REPRO_DEQUANT_IMPL", dequant_impl)
    with pytest.raises(SystemExit) as exc:
        _smoke().main([])
    assert exc.value.code == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert ("REPRO_DEQUANT_IMPL" if dequant_impl else "no TPU found") \
        in out.err


def test_chip_smoke_cases_cover_llama_projections():
    from repro.configs import get_config

    smoke = _smoke()
    cfg = get_config("llama3.2-1b")
    assert smoke.projection_shapes(cfg) == [(2048, 512), (2048, 2048),
                                            (2048, 8192), (8192, 2048)]
    assert sorted({kvh for kvh, _, _ in smoke.paged_cases(cfg)}) == [2, 8]
