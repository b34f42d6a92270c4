"""Convert an elasticdl_tpu serving artifact to a TensorFlow SavedModel.

The docs/design.md "Serving artifact" decision: this framework's native
export is a self-contained signature + streamed per-table memmaps
(serving/export.py) — no TF dependency on the serving side.  This tool
is the documented converter for operators with an existing TF-Serving
fleet (the reference's deployment path, †common/model_handler.py →
SavedModel): it wraps the artifact's forward function with
`jax.experimental.jax2tf`, stores every variable (embedding tables
included) as a `tf.Variable`, and writes a SavedModel whose
serving_default signature takes the model's named feature tensors with
a polymorphic batch dimension.

Parity contract: the SavedModel's outputs match the native
`ServingModel.predict` to float tolerance on the same inputs
(tests/test_savedmodel_export.py re-runs the test_serving parity case
through TF).

Scale caveat: `tf.Variable` materializes each packed table in host
memory during conversion (the native artifact streams; SavedModel's
variable format cannot).  Fine through tens of millions of rows; for
tables beyond host memory, serve the native artifact instead.

Usage:
    python scripts/export_savedmodel.py <artifact_dir> <out_dir> \
        [--model_zoo PATH] [--batch N]

`--batch` sets the example batch used to trace the conversion; the
saved signature itself is batch-polymorphic.
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _example_features(serving_model, batch: int, model_zoo: str = ""):
    """Synthesize a feature pytree matching the model's input signature
    from the zoo module's synthetic reader (every zoo config has one).
    `model_zoo` overrides the artifact's recorded path (same contract as
    load_for_serving — artifacts move between machines)."""
    from elasticdl_tpu.common.model_utils import load_module

    sig = serving_model.signature
    module = load_module(
        model_zoo or sig["model_zoo"] or "model_zoo", sig["model_def"]
    )
    reader_fn = getattr(module, "custom_data_reader", None)
    if reader_fn is None:
        raise ValueError(
            f"{sig['model_def']} has no custom_data_reader to synthesize "
            "an example batch from; pass --sample <npz> instead"
        )
    reader = reader_fn(f"synthetic://sample?n={batch}")
    records = list(
        reader.read_records(type("T", (), {"start": 0, "end": batch}))
    )
    feats = [r[0] if isinstance(r, tuple) else r for r in records]
    if isinstance(feats[0], dict):
        return {
            key: np.stack([f[key] for f in feats]) for key in feats[0]
        }
    return np.stack(feats)


def convert(
    artifact_dir: str,
    out_dir: str,
    model_zoo: str = "",
    batch: int = 4,
    sample: str = "",
):
    import jax
    import tensorflow as tf
    from jax.experimental import jax2tf

    from elasticdl_tpu.serving import load_for_serving
    from elasticdl_tpu.parallel.trainer import model_apply

    served = load_for_serving(artifact_dir, model_zoo=model_zoo, mmap=True)
    if sample:
        loaded = np.load(sample)
        features = (
            {k: loaded[k] for k in loaded.files}
            if len(loaded.files) > 1
            else loaded[loaded.files[0]]
        )
    else:
        features = _example_features(served, batch, model_zoo=model_zoo)

    # Materialize variables (mmap'd packed tables included) as numpy —
    # tf.Variable needs concrete buffers.
    variables = jax.tree.map(np.asarray, served.variables)
    leaves, treedef = jax.tree.flatten(variables)
    model = served._model

    def forward(leaves_, feats):
        vars_ = jax.tree.unflatten(treedef, list(leaves_))
        outputs, _ = model_apply(
            model, vars_, feats, train=False, mutable=False
        )
        return outputs

    def poly(leaf):
        trailing = ", ".join(str(d) for d in np.shape(leaf)[1:])
        return f"(b, {trailing})" if trailing else "(b,)"

    feat_poly = jax.tree.map(poly, features)
    tf_forward = jax2tf.convert(
        forward,
        polymorphic_shapes=[None, feat_poly],
        with_gradient=False,
    )

    class Servable(tf.Module):
        pass

    servable = Servable()
    servable.model_variables = [
        tf.Variable(leaf, trainable=False) for leaf in leaves
    ]

    def spec(leaf, name):
        # Named specs give the SavedModel signature the model's feature
        # names as its tensor kwargs (dense=..., cat=...).
        return tf.TensorSpec(
            (None,) + tuple(np.shape(leaf)[1:]), leaf.dtype, name=name
        )

    if isinstance(features, dict):
        input_signature = [
            {key: spec(value, key) for key, value in features.items()}
        ]
    else:
        input_signature = [spec(features, "input")]

    @tf.function(input_signature=input_signature)
    def serving_fn(feats):
        return {"outputs": tf_forward(servable.model_variables, feats)}

    servable.serving_fn = serving_fn
    tf.saved_model.save(
        servable, out_dir, signatures={"serving_default": serving_fn}
    )

    # Parity gate: the SavedModel must reproduce the native artifact's
    # predictions on the example batch before the conversion counts.
    reloaded = tf.saved_model.load(out_dir)
    tf_in = (
        {k: tf.constant(np.asarray(v)) for k, v in features.items()}
        if isinstance(features, dict)
        else {"input": tf.constant(np.asarray(features))}
    )
    got = reloaded.signatures["serving_default"](**tf_in)[
        "outputs"
    ].numpy()
    want = np.asarray(served.predict(features))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    print(
        f"SavedModel written to {out_dir} "
        f"(parity vs native artifact: max|diff| "
        f"{np.max(np.abs(got - want)):.3g} on batch {len(want)})"
    )
    return out_dir


def main():
    p = argparse.ArgumentParser()
    p.add_argument("artifact_dir")
    p.add_argument("out_dir")
    p.add_argument("--model_zoo", default="")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--sample", default="", help=".npz of example features")
    args = p.parse_args()
    convert(
        args.artifact_dir, args.out_dir,
        model_zoo=args.model_zoo, batch=args.batch, sample=args.sample,
    )


if __name__ == "__main__":
    main()
