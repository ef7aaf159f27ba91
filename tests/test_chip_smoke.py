"""``chip_smoke.py``'s phases at small sizes on the CPU: the script that
proves the main path on a TPU must itself stay runnable.  (On the CPU the
paged kernels run in the interpreter, so the chip-only facts — compiled
kernels, a TPU custom call, ``paged_kernel`` — are reported false here.)"""
import importlib.util
from pathlib import Path

import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_phase(smoke):
    _, checks = smoke.phase_kernels(KV=2, G=5, hd=32, page_size=8, slots=3,
                                    n_table=4, chunk=16)
    assert not checks["compiled"]
    assert checks["decode_max_abs_err"] <= smoke.KERNEL_ATOL
    assert checks["prefill_max_abs_err"] <= smoke.KERNEL_ATOL


def test_serve_phase(smoke):
    tokens, checks = smoke.phase_serve(smoke=True, layers=2, prompts=4,
                                       prompt_len=16, max_new=8)
    assert len(tokens) == 4 and all(len(t) == 8 for t in tokens)
    assert checks["tokens"] == 32


@pytest.mark.parametrize("mesh", [None, "4"])
def test_train_phase(smoke, mesh):
    losses, checks = smoke.phase_train(smoke=True, mesh=mesh, seq_len=16,
                                       global_batch=4)
    assert losses[-1] < losses[0]
    if mesh:
        assert checks["all_reduces"] > 0
