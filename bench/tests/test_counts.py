"""Work counted from shapes: the xLSTM's parameters and training FLOPs,
the passes a QN step requires, and the kernel's bytes per call."""
import json

import jax
import pytest

from bench import counts, manifest


@pytest.fixture(scope="module")
def xlstm():
    return json.loads(manifest.config_path("xlstm-125m").read_text())


def test_param_count_matches_the_program_and_the_published_size(xlstm):
    from repro.models.model import Model
    from bench.entries.qn_train import model_config
    shapes = jax.eval_shape(Model(model_config(xlstm)).init,
                            jax.random.PRNGKey(0))
    program = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    ours = sum(counts.xlstm_param_counts(xlstm).values())
    assert ours == program == 190_652_240


def test_training_flops_per_token(xlstm):
    c = counts.xlstm_param_counts(xlstm)
    matmul = c["mlstm_matmul"] + c["slstm_matmul"] + c["head"]
    seq_term = 10 * 4 * 1536 * 513 / 2
    want = 3 * (2 * matmul + seq_term)
    assert counts.xlstm_train_flops_per_token(xlstm, 512) == want
    assert want == pytest.approx(0.9593e9, rel=1e-4)


def test_a_qn_step_requires_three_passes_per_machine(xlstm):
    assert counts.QN_PASSES_PER_STEP == 3
    per_token = counts.xlstm_train_flops_per_token(xlstm, 512)
    assert counts.qn_step_flops(xlstm, 8, 512) == 3 * 8 * 512 * per_token


@pytest.mark.parametrize("shape,itemsize,n_out,scale,want", [
    ((4, 768), 2, 1, False, 4 * 768 * 2 + 768 * 2),
    ((100, 51, 10), 4, 1, True, 100 * 51 * 10 * 4 + 2 * 100 * 10 * 4),
    ((16, 262144), 4, 3, False, 16 * 262144 * 4 + 3 * 262144 * 4),
    ((3, 5, 4, 7), 4, 1, False, 15 * 4 * 7 * 4 + 15 * 7 * 4),
])
def test_ostat_bytes_from_shapes(shape, itemsize, n_out, scale, want):
    assert counts.ostat_bytes(shape, itemsize, n_out, scale) == want


def test_tree_aggregation_bytes_lays_leaves_out_as_rows_m_cols():
    leaves = [((50304, 768), 2), ((768,), 2), ((8,), 4)]
    want = 5 * ((50304 * 4 * 768 + 50304 * 768) * 2
                + (4 * 768 + 768) * 2 + (4 * 8 + 8) * 4)
    assert counts.tree_aggregation_bytes(leaves, 4, 5) == want
