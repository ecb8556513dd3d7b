"""Whether the served labels are correct, against the plain reference.

Once the window has closed, every finished request is judged. The
reference (float32, each matmul at the precision the configuration
states) runs once over each distinct (pool row, served label) pair, in
blocks of rows. With a million classes
near-ties are common, and a label that rounding moves to a class scored
all but equally is no fault. So each served label is judged by how far
the reference scores it below the reference's own best:

  gap_max         the widest such gap over every answer (reference score
                  units: squared distance in activation space)
  mismatch_share  the share of answers that are not the reference's argmax
  missing         requests that raised or never finished, rejections by
                  a bounded queue aside (limit 0)

The control is the program's own lower-precision path, int8 residency,
switched on (``bench/control.py``); the reference computed in bfloat16,
put in the program's place, is read beside it as a witness.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 64


@functools.lru_cache(maxsize=None)
def _evaluator(reference, precision):
    def run(params, x, lab):
        s = reference(params, x, precision).astype(jnp.float32)
        best = jnp.max(s, axis=-1)
        at = jnp.take_along_axis(s, lab[:, None], axis=-1)[:, 0]
        return best - at, jnp.argmax(s, axis=-1)
    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _lower_precision_labels(reference, precision, dtype):
    return jax.jit(lambda params, x: jnp.argmax(
        reference(params, x, precision, dtype), axis=-1))


def _blocks(x: np.ndarray, lab: np.ndarray):
    """Fixed-size blocks (the last one zero-padded), so one program serves
    every block."""
    for i in range(0, len(x), BLOCK):
        xb, lb = x[i:i + BLOCK], lab[i:i + BLOCK]
        k = len(xb)
        if k < BLOCK:
            xb = np.concatenate([xb, np.zeros((BLOCK - k,) + xb.shape[1:],
                                              xb.dtype)])
            lb = np.concatenate([lb, np.zeros(BLOCK - k, lb.dtype)])
        yield k, xb, lb


def readings(reference, precision: tuple, params: dict, pool: np.ndarray,
             rows: np.ndarray, labels: np.ndarray) -> dict:
    """Gap and mismatch numbers over answers ``labels[i]`` to pool rows
    ``rows[i]``; the reference runs once per distinct pair."""
    if rows.size == 0:
        return {"gap_max": 0.0, "mismatch_share": 0.0}
    pairs, inv = np.unique(np.stack([rows, labels], axis=1), axis=0,
                           return_inverse=True)
    inv = inv.reshape(-1)
    ev = _evaluator(reference, precision)
    gaps, args = [], []
    for k, xb, lb in _blocks(pool[pairs[:, 0]],
                             pairs[:, 1].astype(np.int32)):
        g, a = ev(params, xb, lb)
        gaps.append(np.asarray(g)[:k])
        args.append(np.asarray(a)[:k])
    gap = np.concatenate(gaps)[inv]
    best = np.concatenate(args)[inv]
    return {"gap_max": float(gap.max()),
            "mismatch_share": float(np.mean(best != labels))}


def control_labels(reference, precision: tuple, params: dict,
                   pool: np.ndarray, rows: np.ndarray,
                   dtype=jnp.bfloat16) -> np.ndarray:
    """The label the reference computed in ``dtype`` puts first, for each
    of ``rows`` (computed once per distinct row)."""
    fn = _lower_precision_labels(reference, precision, dtype)
    uniq, inv = np.unique(rows, return_inverse=True)
    out = []
    for k, xb, _ in _blocks(pool[uniq], np.zeros(uniq.size, np.int32)):
        out.append(np.asarray(fn(params, xb))[:k])
    return np.concatenate(out)[inv.reshape(-1)] if out else \
        np.zeros(0, np.int64)


def judge(numbers: dict, limits: dict) -> bool:
    """Every number within its limit; a limit not yet set fails."""
    return all(limits[k] is not None and numbers[k] <= limits[k]
               for k in limits)


def check_labels(config: dict, maker, built, window, seed: int) -> dict:
    from bench.modes.serve_open_loop import DONE, REJECTED
    from bench.reference import stated
    limits = {"missing": 0, **config["correct"]["limits"]}
    done = window.status == DONE
    rows, served = window.row[done], window.label[done]
    got = readings(maker.reference_scores, stated(config, maker.STAGES),
                   built.params, built.pool, rows, served)
    numbers = {
        "missing": int(np.sum((window.status != DONE)
                              & (window.status != REJECTED))),
        "gap_max": got["gap_max"],
        "mismatch_share": got["mismatch_share"],
    }
    info = {"answers_judged": int(done.sum())}
    if built.labels is not None and rows.size:
        info["served_accuracy"] = float(np.mean(served
                                                == built.labels[rows]))
    return {"correct": judge(numbers, limits) and bool(done.any()),
            "checks": {k: {"value": numbers[k], "limit": limits[k]}
                       for k in limits},
            "info": info}
