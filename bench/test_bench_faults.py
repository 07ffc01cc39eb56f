"""The comparison that decides ``correct`` fails a broken program: the CPU
rehearsal of a run, with the served step broken underneath, comes out
not correct for each fault a serving cell can have; and the float8
control, judged by the same test, is not correct either."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from test_bench_harness import REHEARSE, rehearse  # noqa: E402


def _rejit(executor, wrap):
    """Replace the jitted step (prefill and decode alike) by ``wrap`` of
    the program's own step function."""
    import jax
    step = executor._prefill_step
    executor._prefill_jit = jax.jit(
        lambda params, stores, *a: wrap(step(params, stores, *a), stores),
        donate_argnums=(1,))


def state_unchanged(executor):
    """The step computes its tokens but returns the KV pool it was given:
    no key or value is ever written."""
    _rejit(executor, lambda out, stores: (out[0], stores))


def token_altered(executor):
    """Every token is altered where it is produced."""
    vocab = executor.cfg.vocab_size
    _rejit(executor, lambda out, stores: ((out[0] + 1) % vocab, out[1]))


def half_batch(executor):
    """Rows in the second half of each batch get the first row's token,
    as if only half the batch were computed."""
    import jax.numpy as jnp

    def wrap(out, stores):
        ids = out[0]
        b = ids.shape[0]
        return jnp.where(jnp.arange(b) >= max(1, b // 2), ids[0], ids), out[1]
    _rejit(executor, wrap)


@pytest.mark.parametrize("fault", [state_unchanged, token_altered,
                                   half_batch])
def test_a_broken_step_is_not_correct(fault):
    # the gather path: the fault is in the step, not in the kernels
    result, _text = rehearse(False, seconds=5.0, fault=fault,
                             attn_impl=None)
    assert result["correct"] is False
    gap = result["compared"]["logit_gap"]
    assert gap["value"] is not None and gap["value"] > gap["limit"]


def test_control_is_not_correct():
    """The control, put in the program's place on the same prompts and
    served tokens, fails the run's own test against the configuration's
    limit, where the sound program passes it."""
    import io

    import run
    from harness import check
    c = run.Cell("qwen2vl2b.mh.steady",
                 rehearse=dict(REHEARSE, attn_impl=None), out=io.StringIO())
    c.build(11)
    c.warm()
    w = c.window(11, 8.0, trace=False)
    del w["engine"]
    c.free_program()
    res = c.score(11, w["rec"], control=True)
    assert res["requests"] > 0
    assert check.judge(res, c.limit)[0] is True
    assert check.judge(res, c.limit, gap="control_gap")[0] is False
    assert res["control_gap"] > res["logit_gap"]
