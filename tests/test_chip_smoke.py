"""`chip_smoke.py` rehearsed on the CPU at a tiny size.

The script itself refuses to run without a TPU; these tests drive its
phases directly on the smallest synthetic part, so a wrong path, argument
or check fails here before it costs chip time.  The compiled-kernel check
(`tpu_custom_call` in each pool's step) needs the TPU backend and is left
to the chip run; `tests/test_tpu_compile.py` compiles the same kernels for
a described v5e.
"""
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import chip_smoke  # noqa: E402


def test_refuses_without_tpu():
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)


def test_serve_phase_tiny(capsys):
    sch, stats, results, _ = chip_smoke.serve(
        device="xcvu_test", n_jobs=3, slots=2, pop=8, budget=4,
        gens_per_step=2)
    assert len(results) == 4                    # 3 unfused + 1 fused job
    assert len(sch.pools()) == 2
    assert stats["fleet"]["jobs_failed"] == 0


@pytest.mark.multidevice
def test_four_chips_phase_tiny():
    if jax.device_count() % 2:
        pytest.skip("needs an even device count")
    text = chip_smoke.four_chips(device="xcvu_test", pop=8,
                                 n_islands=2 * jax.device_count(),
                                 migrate_every=2, n_gens=4)
    assert "collective-permute" in text
