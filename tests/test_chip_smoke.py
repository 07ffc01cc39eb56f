"""The chip smoke script's CPU rehearsal: the same phases it runs on the TPU
(build_stack -> Engine.run -> kernel vs gather), at the reduced size with
interpreted kernels, so a change that breaks the script is caught here
instead of on the chip."""
import importlib.util
from pathlib import Path


def _load_chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_rehearsal_serves_and_matches_kernels():
    cs = _load_chip_smoke()
    executor, _setup_s, _serve_s = cs.serve_phase(rehearse=True)
    assert not executor.legacy and len(executor.emitted) == cs.N_REQUESTS
    executor._stores = None
    # raises on a logit gap past LOGIT_ATOL or a token split outside a tie
    diff, _agree, compared = cs.compare_attention_paths(executor)
    assert diff <= cs.LOGIT_ATOL
    assert compared == len(cs.COMPARE_PROMPTS) * (1 + cs.DECODE_STEPS)
