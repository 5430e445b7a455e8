"""The plain reference against the program's own full-sequence forward at
a reduced size: same weights (made by the benchmark), same logits."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference, run, weights

DATA = run.BENCH / "tests" / "data"


@pytest.mark.parametrize("frac,tied", [(1.0, True), (0.5, False)],
                         ids=["granite_like", "glm_like"])
def test_reference_matches_model_forward(frac, tied):
    from repro.models import model as M
    from repro.models.layers import logits_from_hidden
    spec = run.load_config("tiny", DATA)
    spec["model"].update(rope_fraction=frac, tie_embeddings=tied)
    model = spec["model"]
    cfg = run.arch_for(spec).program_config(spec)
    params = weights.make_params(model, 2 ** 34 + 3)
    toks = np.random.default_rng(0).integers(0, model["vocab"], 40)
    with jax.default_matmul_precision("highest"):
        h, _ = M.forward(cfg, params, {"tokens": jnp.asarray(toks)[None]})
        want = np.asarray(logits_from_hidden(params, h, cfg))[0,
                                                              :, :model["vocab"]]
    at = [0, 7, 39]
    got = reference.logits(model, params, toks, at)
    np.testing.assert_allclose(got, want[at], atol=2e-4, rtol=2e-4)


def test_weights_match_the_engines_layout():
    from repro.serving.paged_attn import init_serving_params
    spec = run.load_config("tiny", DATA)
    cfg = run.arch_for(spec).program_config(spec)
    ours = jax.eval_shape(lambda: weights.make_params(spec["model"], 1))
    theirs = jax.eval_shape(lambda k: init_serving_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_served_gaps_zero_for_greedy_tokens():
    spec = run.load_config("tiny", DATA)
    model = spec["model"]
    params = weights.make_params(model, 5)
    prompt = np.arange(1, 20) % model["vocab"]
    seq = list(prompt)
    for _ in range(6):
        lg = reference.logits(model, params, np.asarray(seq), [len(seq) - 1])
        seq.append(int(lg[0].argmax()))
    gaps = reference.served_gaps(model, params, prompt, seq[len(prompt):])
    assert np.all(gaps == 0)
    wrong = list(seq[len(prompt):])
    wrong[2] = (wrong[2] + 1) % model["vocab"]
    assert reference.served_gaps(model, params, prompt, wrong)[2] > 0
