"""FLOPs and bytes from shapes (``bench/work``), against the weights the
benchmark makes and against hand counts."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench.reference import dense as ref_dense
from bench.reference import ssm as ref_ssm
from bench.work import dense, ssm

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def _leaf_count(ref, m):
    shapes = jax.eval_shape(lambda k: ref.init(m, k), jax.random.PRNGKey(0))
    return sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))


@pytest.mark.parametrize("name,ref,work,count", [
    ("olmo-1b", ref_dense, dense, 1_176_764_416),
    ("mamba2-130m", ref_ssm, ssm, 128_983_488),
])
def test_params_match_the_weights(name, ref, work, count):
    m = _model(name)
    assert work.params(m) == count == _leaf_count(ref, m)


def test_dense_decode_counts():
    m = _model("olmo-1b")
    n = dense.params(m)
    kv = 2 * 16 * 16 * 128 * 4
    assert dense.kv_bytes_per_token(m, 4) == kv == 256 * 1024
    flops, nbytes = dense.decode(m, [100, 1000], 4)
    assert flops == 2 * (2.0 * n) + 4.0 * 16 * 16 * 128 * (101 + 1001)
    assert nbytes == 4 * n + kv * (101 + 1001)
    f1, b1 = dense.decode(m, [], 4)
    assert f1 == 0 and b1 == 4 * n


def test_dense_prefill_counts():
    m = _model("olmo-1b")
    flops, nbytes = dense.prefill(m, 1024, 4)
    assert flops == 2.0 * dense.params(m) * 1024 + 2.0 * 16 * 16 * 128 * 1024 ** 2
    assert nbytes == 4 * dense.params(m) + 256 * 1024 * 1024
    assert dense.kv_write_bytes(m, 1024, 4) == 256 * 1024 * 1024


def test_ssm_counts():
    m = _model("mamba2-130m")
    st = 24 * (24 * 128 * 64 + 3 * 1792) * 4
    assert ssm.state_bytes(m, 4) == st
    flops, nbytes = ssm.decode(m, [5, 500, 1500], 4)
    assert flops == 3 * (2.0 * ssm.params(m) + 4.0 * 24 * 128 * 64 * 24)
    assert nbytes == 4 * ssm.params(m) + 3 * 2 * st
    pf, pb = ssm.prefill(m, 64, 4)
    assert pf == 64 * (2.0 * ssm.params(m) + 4.0 * 24 * 128 * 64 * 24)
    assert ssm.kv_write_bytes(m, 64, 4) == 0.0
