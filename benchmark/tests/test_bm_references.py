"""The plain reference against apex_tpu.models at a tiny size in float32, and
its controls: the reference one precision down has to fall outside the limit
that sound runs stay inside."""

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import models
from lib import traffic as tg, weights
from references import bert as ref_bert

BERT = {"vocab_size": 1024, "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
        "intermediate_size": 128, "layer_norm_eps": 1e-12}


def _shapes(model):
    return jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))


def test_bert_reference_matches_the_model_in_float32():
    model = models.BertForPretraining(models.BertConfig(
        vocab_size=1024, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        intermediate_size=128))
    params = weights.make_weights(_shapes(model), seed=2**31 + 3, std=0.05)
    ids, labels, nsp = tg.mlm_nsp_batch({"seq_len": 32, "mask_prob": 0.15, "mask_token_id": 3,
                                         "first_token_id": 5}, 11, 0, 4, 1024)
    with jax.default_matmul_precision("highest"):
        (mlm, nsp_logits), _ = model.apply(params, jnp.asarray(ids))
        want = model.loss(params, jnp.asarray(ids), jnp.asarray(labels), jnp.asarray(nsp))
    r_mlm, r_nsp = ref_bert.logits(params, jnp.asarray(ids), BERT)
    np.testing.assert_allclose(np.asarray(r_mlm), np.asarray(mlm), atol=2e-5)
    np.testing.assert_allclose(np.asarray(r_nsp), np.asarray(nsp_logits), atol=2e-5)
    w = ref_bert.token_weights(labels, 1)
    got = ref_bert.weighted_loss(params, jnp.asarray(ids), jnp.asarray(labels), jnp.asarray(nsp),
                                 jnp.asarray(w), 1.0 / 4, BERT)
    assert abs(float(got) - float(want)) < 1e-4


def test_bert_token_weights_follow_data_parallel_means():
    labels = np.full((4, 6), -100)
    labels[0, :3] = 7          # chip 0 has 3 masked tokens, chip 1 has 1
    labels[3, 0] = 9
    w = ref_bert.token_weights(labels, groups=2)
    assert w[0, 0] == 1 / 6 and w[3, 0] == 1 / 2 and w.sum() == 1.0


def test_bert_controls_fail_where_float32_passes():
    """The control at a size a test can hold.  The limits in references/bert.py
    are the chip-size cell's (PERF.md has the readings); here the same
    separation is shown relatively: on three seeds, the reference one precision
    down (fp8-rounded matmuls) moves the first gradient's norm at least three
    times as far as the stated precision (bf16) does, and a bf16 parameter
    store, a stuck step and a part of the batch left out each break the limit
    of the number that is there to catch them."""
    model = models.BertForPretraining(models.BertConfig(
        vocab_size=1024, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        intermediate_size=128))
    p = {"seq_len": 32, "mask_prob": 0.15, "mask_token_id": 3, "first_token_id": 5}
    for seed in (5, 6, 7):
        params = weights.make_weights(_shapes(model), seed=seed, std=0.02)
        batches = [tg.mlm_nsp_batch(p, seed, i, 4, 1024) for i in range(3)]
        ref = ref_bert.train(params, batches, BERT)
        sound = ref_bert.compare(ref_bert.train(params, batches, BERT, precision="bfloat16"), ref)
        low = ref_bert.compare(ref_bert.train(params, batches, BERT, precision="fp8"), ref)
        assert all(sound[k] < ref_bert.LIMITS[k] for k in ref_bert.LIMITS)
        assert low["grad_norm_gap"] > 3 * sound["grad_norm_gap"]
    again = ref_bert.compare(ref_bert.train(params, batches, BERT, block_rows=4), ref)
    assert max(again[k] for k in ref_bert.LIMITS) < 1e-4      # blocks only reorder the sums
    half = ref_bert.compare(ref_bert.train(params, batches, BERT, param_dtype="bfloat16"), ref)
    assert half["update_norm_gap"] > ref_bert.LIMITS["update_norm_gap"]
    stuck = dict(ref, update_norms=np.zeros_like(ref["update_norms"]))
    assert ref_bert.compare(stuck, ref)["update_norm_gap"] > ref_bert.LIMITS["update_norm_gap"]
    part = ref_bert.train(params, [tuple(x[:2] for x in b) for b in batches], BERT)
    assert ref_bert.compare(part, ref)["first_loss_gap"] > ref_bert.LIMITS["first_loss_gap"]
