"""The two plain references against the program at a tiny size on the
CPU: BERT's predict-mode pretraining loss, and the NMT server's prefill
then paged decode against the reference's full teacher-forced forward."""

import pytest

from benchmarks.lib import harness, models, serving

ROOT = harness.ROOT


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("MXTPU_PALLAS_INTERPRET", "1")


# float32: program and reference do the same arithmetic in another order,
# and the loss is a mean of ~6 nats: 1e-5 relative leaves a decade over
# the 1e-6 seen. bfloat16 keeps 8 bits of mantissa and this tiny loss
# averages only 24 + 3 terms: 1.5e-4 seen, 2e-3 allowed (the chip run's
# mean over 2464 terms is held to 1e-3, pretrain_s512.json).
@pytest.mark.parametrize("dtype, tol", [("float32", 1e-5),
                                        ("bfloat16", 2e-3)])
def test_bert_loss_against_the_reference(interpret, dtype, tol):
    from benchmarks.kinds import train_job
    cfg = harness.load_json(ROOT, "benchmarks/configs/bert_base.json")
    cfg.update(hidden_size=128, num_hidden_layers=2, num_attention_heads=2,
               intermediate_size=256, vocab_size=512, param_dtype=dtype)
    model = models.build_bert(cfg, 7, 128)
    batch = models.bert_batches(cfg, 7, 1, 3, 128, 8, [70, 128])[0]
    got, want = train_job.predict_loss(model, cfg, batch)
    assert abs(got - want) <= tol * abs(want), (got, want)
    # the reference is sensitive to what it is given: other labels, other
    # loss
    other = batch[:4] + (batch[4][:, ::-1].copy(), batch[5])
    assert abs(train_job.predict_loss(model, cfg, other)[1] - want) > 1e-3


def test_nmt_prefill_then_decode_against_the_full_forward(interpret):
    """Logits, not tokens. float32 on the CPU: 1e-5 of the largest logit
    leaves a decade over the 3e-7 seen; a bf16 matmul anywhere would
    show at 1e-3."""
    cfg = harness.load_json(ROOT, "benchmarks/configs/nmt_base.json")
    cfg.update(d_model=64, encoder_layers=2, decoder_layers=2,
               attention_heads=2, ffn_dim=128, vocab_size=200)
    cfg["server"].update(slots=4, page_size=8)
    model, srv = serving.build_server(cfg, 11, 16)
    try:
        err = serving.logit_check(srv, model, cfg, 11,
                                  {"requests": 4, "positions": 16})
        assert err <= 1e-5, err
        assert srv.pool.in_use() == 0
        # and it does compare: the server holds a snapshot of the weights,
        # so a weight changed in the model reaches only the reference
        w = model.decoder.layers[0].ffn.ffn1.weight
        w.set_data(w.data() * 1.5)
        assert serving.logit_check(srv, model, cfg, 11, {
            "requests": 4, "positions": 16}) > 1e-3
    finally:
        srv.close()
