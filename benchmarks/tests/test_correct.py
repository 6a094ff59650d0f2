"""``correct`` comes out false where it has to.

The rest of a run is driven without the harness's look for a chip (the loop
is called directly, at tiny sizes on the CPU), with the timed path broken
underneath: once for each fault a one-chip training cell can have. And the
control: the reference in float8, put in the program's place, is not
correct under the cells' own limits.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks import check_train, run as run_mod, traffic
from benchmarks import check_serve
from benchmarks.loops import serve_closed, train_fit
from benchmarks.reference import perceiver as ref
from benchmarks.tests import tiny
from benchmarks.weights import make_weights_fn, seed_words, train_rng

CELLS = {"mlm": tiny.mlm, "images": tiny.images}
SERVED = {"fillmask": tiny.fillmask}


def _run(make, break_program=None, dtype="float32"):
    cell, cfg, mix, builder = make(dtype)
    loop = {"train_fit": train_fit, "serve_closed": serve_closed}[mix["loop"]]
    start = time.time()
    probes = run_mod.Probes(clock=lambda: time.time() - start, compiles=lambda: 0)
    return loop.run(cell, cfg, mix, builder, 2**31 + 17, 0.5, False, probes,
                    break_program=break_program)


def state_unchanged(trainer):
    """A step that returns its state unchanged (its metrics still flow)."""
    inner = trainer._train_step

    def step(state, batch):
        _, metrics = inner(jax.tree.map(jnp.copy, state), batch)
        return state, metrics

    trainer._train_step = step


def half_batch(trainer):
    """Half of the batch left out, the mean taken over the rest: the second
    half of every batch is overwritten with the first."""
    inner = trainer._train_step

    def step(state, batch):
        def fold(x):
            half = len(x) // 2
            return jnp.concatenate([x[:half], x[:half]])

        return inner(state, {k: fold(jnp.asarray(v)) for k, v in batch.items()})

    trainer._train_step = step


def altered_token(server):
    """A token altered where it is produced: the first of every top-k is
    replaced by a fixed word of the vocabulary."""
    inner = server._topk_transform

    def topk(n_masks, k):
        transform = inner(n_masks, k)

        def altered(logits):
            out = transform(logits)
            for per_mask in out:
                per_mask[0] = "waaaa"
            return out

        return altered

    server._topk_transform = topk


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(name):
    result = _run(CELLS[name])
    assert result["verdict"]["correct"], result["verdict"]
    assert result["steps"] > 0 and result["verdict"]["compared"]["steps_missing"]["value"] == 0


def test_sound_served_run_is_correct():
    result = _run(SERVED["fillmask"])
    assert result["verdict"]["correct"], result["verdict"]
    assert result["requests_answered"] > 0 and result["failed"] == 0
    assert result["engine_counts"]["rows"] == result["attempted"]


def test_altered_served_token_is_not_correct():
    result = _run(SERVED["fillmask"], break_program=altered_token)
    assert not result["verdict"]["correct"], result["verdict"]


def test_float8_control_of_served_tokens_is_not_correct():
    import numpy as np

    cell, cfg, mix, builder = SERVED["fillmask"]()
    weights_fn = make_weights_fn(builder.param_shapes(cfg))
    logits_fn = builder.reference_logits_fn(cfg)
    failed = 0
    for seed in (3, 4, 5):
        lo, hi = seed_words(seed)
        picked = traffic.make_requests(mix, cfg["vocab_size"], seed)[:48]
        args = (weights_fn(lo, hi), picked, cfg["max_seq_len"], 4)
        reference = check_serve.reference_mask_logits(logits_fn(ref.F32), *args)
        low = check_serve.reference_mask_logits(logits_fn(ref.Arith(jnp.float8_e4m3fn)), *args)
        numbers = check_serve.gaps_below_best(reference, [np.argmax(x, axis=-1) for x in low])
        failed += not check_serve.verdict(numbers, check_serve.load_limits(cell["name"]))["correct"]
    assert failed == 3


@pytest.mark.parametrize("name", sorted(CELLS))
@pytest.mark.parametrize("fault", [state_unchanged, half_batch], ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(name, fault):
    result = _run(CELLS[name], break_program=fault)
    assert not result["verdict"]["correct"], result["verdict"]


@pytest.mark.parametrize("name", sorted(CELLS))
def test_float8_control_is_not_correct(name):
    cell, cfg, mix, builder = CELLS[name]()
    task = builder.reference_task(cfg)
    weights_fn = make_weights_fn(builder.param_shapes(cfg))
    failed = 0
    for seed in (3, 4, 5):
        lo, hi = seed_words(seed)
        rng = train_rng(lo, hi)
        batches = traffic.make_batches(mix, seed)[:check_train.STEPS]
        reference = check_train.reference_readings(task, weights_fn(lo, hi), rng, batches)
        control = check_train.reference_readings(
            task, weights_fn(lo, hi), rng, batches, arith=ref.Arith(jnp.float8_e4m3fn))
        verdict = check_train.verdict(check_train.compare(control, reference),
                                      check_train.load_limits(cell["name"]))
        failed += not verdict["correct"]
    assert failed == 3
