import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudl.export import (
    artifact_sizes,
    check_parity,
    export_stablehlo,
    latency_benchmark,
    load_exported,
    load_params,
    save_params,
)


def _fn(x, w):
    return jnp.tanh(x @ w)


@pytest.fixture
def args(rng_np):
    return (
        rng_np.normal(size=(4, 8)).astype(np.float32),
        rng_np.normal(size=(8, 3)).astype(np.float32),
    )


def test_stablehlo_roundtrip(tmp_path, args):
    path = str(tmp_path / "model.stablehlo")
    blob = export_stablehlo(_fn, args, path=path)
    assert len(blob) > 0
    restored = load_exported(path)
    np.testing.assert_allclose(
        np.asarray(restored(*args)), np.asarray(_fn(*args)), rtol=1e-5
    )


def test_stablehlo_multiplatform(args):
    blob = export_stablehlo(_fn, args, platforms=("cpu", "tpu"))
    restored = load_exported(blob)
    np.testing.assert_allclose(
        np.asarray(restored(*args)), np.asarray(_fn(*args)), rtol=1e-5
    )


def test_params_roundtrip(tmp_path):
    params = {"dense": {"kernel": jnp.ones((3, 2)), "bias": jnp.zeros((2,))}}
    path = str(tmp_path / "ckpt")
    save_params(path, params)
    restored = load_params(path, like=params)
    np.testing.assert_array_equal(
        np.asarray(restored["dense"]["kernel"]), np.ones((3, 2))
    )
    sizes = artifact_sizes(path)
    assert sizes[path] > 0


def test_artifact_sizes_missing_file(tmp_path):
    missing = str(tmp_path / "nope.bin")
    assert artifact_sizes(missing)[missing] is None


def test_check_parity_same_backend(args):
    report = check_parity(
        _fn, args, device_a=jax.devices()[0], device_b=jax.devices()[0]
    )
    assert report.ok, str(report)
    assert report.max_abs_err < 1e-6


def test_compare_outputs_detects_mismatch():
    from tpudl.export.parity import compare_outputs

    a = {"logits": np.ones((4,), np.float32)}
    b = {"logits": np.ones((4,), np.float32) + 0.01}
    report = compare_outputs(a, b, rtol=1e-5, atol=1e-4)
    assert not report.ok
    assert report.max_abs_err == pytest.approx(0.01, rel=1e-3)
    good = compare_outputs(a, a, rtol=1e-5, atol=1e-4)
    assert good.ok and "PASS" in str(good)


def test_latency_benchmark_shape(args):
    result = latency_benchmark(_fn, args, warmup=1, iters=3)
    assert result["iters"] == 3
    assert result["compute"]["mean_ms"] >= 0.0
    assert result["transfer"]["p95_ms"] >= 0.0


def test_resnet_export_load_parity(tmp_path, rng_np):
    """The reference's signature behavior as a pytest guard: the flagship
    CV model family through export -> load -> numerical parity at the
    reference tolerances (reference notebooks/cv/onnx_experiments.py:
    33-42 export, :81 load, :142-144 allclose), on a tiny ResNet."""
    from tpudl.models import ResNet
    from tpudl.models.resnet import ResNetBlock

    model = ResNet(
        stage_sizes=(1, 1), block_cls=ResNetBlock, num_classes=10,
        num_filters=8, dtype=jnp.float32, small_inputs=True,
    )
    x = rng_np.normal(size=(2, 16, 16, 3)).astype(np.float32)
    variables = model.init(jax.random.key(0), jnp.asarray(x), train=False)

    def forward(params, batch_stats, images):
        return model.apply(
            {"params": params, "batch_stats": batch_stats}, images, train=False
        )

    args = (variables["params"], variables["batch_stats"], jnp.asarray(x))
    path = str(tmp_path / "resnet.stablehlo")
    export_stablehlo(forward, args, path=path)
    restored = load_exported(path)
    np.testing.assert_allclose(
        np.asarray(restored(*args)),
        np.asarray(forward(*args)),
        rtol=1e-5,
        atol=1e-4,  # the reference's parity contract
    )


def test_bert_export_load_parity(tmp_path, rng_np):
    """The NLP family through the same export -> load -> parity guard
    (the deployment artifact for configs[1]/[3] fine-tuned classifiers)."""
    from tpudl.models.bert import BERT_TINY, BertForSequenceClassification

    cfg = BERT_TINY(
        num_labels=2, dtype=jnp.float32,
        hidden_dropout=0.0, attention_dropout=0.0,
    )
    model = BertForSequenceClassification(cfg)
    ids = jnp.asarray(
        rng_np.integers(0, cfg.vocab_size, size=(2, 16)), jnp.int32
    )
    mask = jnp.ones_like(ids)
    params = model.init(jax.random.key(0), ids, train=False)["params"]

    def forward(params, input_ids, attention_mask):
        return model.apply(
            {"params": params}, input_ids, attention_mask, train=False
        )

    args = (params, ids, mask)
    path = str(tmp_path / "bert.stablehlo")
    export_stablehlo(forward, args, path=path)
    restored = load_exported(path)
    np.testing.assert_allclose(
        np.asarray(restored(*args)),
        np.asarray(forward(*args)),
        rtol=1e-5,
        atol=1e-4,  # the reference's parity contract
    )
