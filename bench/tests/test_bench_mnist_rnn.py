"""The ``mnist_rnn`` configuration: the application handed to the flow
computes the published network, and flexible matching offloads what the
configuration says."""
import jax
import numpy as np
import pytest

from bench import cell as C
from bench import check


@pytest.fixture(scope="module")
def parts():
    cell = C.resolve("mnist_rnn.closed")
    app = C.load_module(cell.config_dir / cell.config["app"])
    params = C.make_params(cell, 2**33 + 5)
    return cell, app, params


def _inputs(cell, n):
    return C.sample_inputs(cell, 2**33 + 5, 0, n, (28, 1, 28))


def test_exported_program_is_the_published_network(parts):
    """The IR over the exported weights (biases summed, batch norm folded,
    last step picked), interpreted in float32, against the reference."""
    from repro.core import ir

    cell, app, params = parts
    expr, weights = app.build(cell.config), app.program_weights(params, cell.config)
    xs = _inputs(cell, 6)
    got = np.stack([np.asarray(ir.interpret(expr, dict(weights, x=x)))[0] for x in xs])
    with jax.default_matmul_precision("highest"):
        ref = check.reference_outputs(cell, params, xs)
    assert np.max(check.rel_errors(got, ref)) < 1e-5


def test_matching_offloads_what_the_configuration_says(parts):
    from repro.core import ir
    from repro.core.compile import compile_program

    cell, app, _ = parts
    program = compile_program(app.build(cell.config)).program
    ops = {}
    for n in ir.postorder(program):
        if isinstance(n, ir.Call) and ir.accel_op_target(n.op):
            ops[n.op] = ops.get(n.op, 0) + 1
    assert ops == cell.config["offloads"]


def test_nothing_is_reduced_from_the_source():
    spec = C.load_spec()
    cfg = next(c for c in spec["configs"] if c["name"] == "mnist_rnn")
    assert cfg["reduced"] == [] and C.resolve("mnist_rnn.closed").config["reduced"] == {}


@pytest.mark.parametrize("precision", ["int4", "bfloat16"])
def test_lower_precisions_depart_from_the_reference(parts, precision):
    cell, _, params = parts
    xs = _inputs(cell, 8)
    ref = check.reference_outputs(cell, params, xs)
    low = check.reference_outputs(cell, params, xs, precision)
    assert 0 < np.max(check.rel_errors(low, ref)) < 1.5
