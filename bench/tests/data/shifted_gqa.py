"""Test-only architecture module: the dense GQA block with a planted shift
in its reference (each row of logits rolled by one vocabulary id), and one
kernel more than ``dense_gqa`` names: the MLP's matmul fusion, with the
work of its three matmuls.  Placed in ``bench/models/`` of a copy of the
benchmark and named by a configuration there, it shows that an
architecture is added as files only; its shift must make ``correct``
false."""
import importlib.util
from pathlib import Path

import numpy as np

_MODELS = next(p / "models" for p in Path(__file__).resolve().parents
               if (p / "models" / "dense_gqa.py").is_file())
_spec = importlib.util.spec_from_file_location(
    "bench_dense_gqa_for_shifted", _MODELS / "dense_gqa.py")
dense = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dense)

MLP_OP = "convolution_reduce_fusion"
KERNELS = dense.KERNELS + (MLP_OP,)

model_config = dense.model_config
shapes = dense.shapes
step_flops = dense.step_flops


def logits(params, p, tokens, positions, mode="f32"):
    return np.roll(dense.logits(params, p, tokens, positions, mode), 1,
                   axis=-1)


def layer_work(seqs, m):
    tokens = sum(int(s.positions.size) for s in seqs)
    d, f = m["d_model"], m["d_ff"]
    mlp = (2.0 * 3 * d * f * tokens, 2.0 * (3 * d * f + 3 * tokens * d))
    return [dict(w, **{MLP_OP: mlp}) for w in dense.layer_work(seqs, m)]
