"""The check that decides ``correct``: what the window served, against the
configuration's plain reference.

Every answered request of the window is compared, by request index: its
served outputs against the reference's forward pass over the same inputs
and the same published weights (the program is given its own inputs made
from them). The reference imports nothing of the program and runs after
the window has closed and the device's peak memory was read.

Numbers compared, each against its limit (``value <= limit`` passes):

* ``max_rel_err``: the widest per-sample relative error of the served
  logits, ``||served - reference|| / ||reference||``; the limit is the
  configuration's ``limits.max_rel_err``, set from readings of sound runs
  and of the control (PERF.md).
* ``unanswered``: requests due in the window that never came back done
  (rejected, failed, or not answered within the grace period); limit 0.
* ``offload_mismatch``: intrinsics whose call count in the matched program
  differs from the configuration's ``offloads``: the check then covers
  other simulators than the cell says; limit 0.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Tuple

import numpy as np

#: samples per reference call: one compiled shape, padded at the end
BLOCK = 64


def reference_outputs(cell, params: Dict[str, np.ndarray], xs: List[np.ndarray],
                      precision: str = "float32") -> np.ndarray:
    """The reference's outputs for every input, in blocks of ``BLOCK``
    samples (each block stacks the program's per-sample inputs); a
    ``precision`` other than float32 gives a control's outputs."""
    import jax

    params = {k: jax.numpy.asarray(v) for k, v in params.items()}
    fwd = jax.jit(lambda p, x: cell.reference.forward(p, x, cell.config, precision))
    out = []
    for i in range(0, len(xs), BLOCK):
        blk = list(xs[i:i + BLOCK])
        n = len(blk)
        blk += [blk[-1]] * (BLOCK - n)
        out.append(np.asarray(fwd(params, np.stack(blk)))[:n])
    return np.concatenate(out) if out else np.zeros((0,))


def rel_errors(served: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per-sample ``||served - ref|| / ||ref||`` (float64)."""
    s = served.reshape(len(served), -1).astype(np.float64)
    r = ref.reshape(len(ref), -1).astype(np.float64)
    return np.linalg.norm(s - r, axis=1) / np.linalg.norm(r, axis=1)


def compare(cell, params, served: Dict[int, Tuple[List, List]],
            offloads: Dict[str, int], unanswered: int, log=print) -> Dict:
    """``served`` maps request index -> (inputs, outputs). Returns the
    numbers compared, each with its limit, and logs them as the last lines
    of standard error. A value of None (nothing to compare) fails."""
    xs, outs = [], []
    for idx in sorted(served):
        inputs, outputs = served[idx]
        if outputs is None or len(outputs) != len(inputs):
            unanswered += 1
            continue
        xs += list(inputs)
        outs += [np.asarray(o) for o in outputs]
    want = cell.config["offloads"]
    mismatch = sum(offloads.get(k, 0) != v for k, v in want.items()) + \
        sum(k not in want for k in offloads)
    if outs:
        err = float(rel_errors(np.stack(outs), reference_outputs(cell, params, xs)).max())
    else:
        err = None  # nothing answered: nothing passes
    checks = {
        "max_rel_err": {"value": err, "limit": cell.config["limits"]["max_rel_err"],
                        "samples": len(outs)},
        "unanswered": {"value": unanswered, "limit": 0},
        "offload_mismatch": {"value": mismatch, "limit": 0},
    }
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    return checks


def passed(checks: Dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
