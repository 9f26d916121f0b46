"""``rows`` × ``cols`` 4-connected mesh, node r·cols + c at row r, column c."""
import numpy as np


def edges(spec: dict):
    rows, cols = spec["rows"], spec["cols"]
    idx = np.arange(rows * cols, dtype=np.int64).reshape(rows, cols)
    right = np.stack([idx[:, :-1].ravel(), idx[:, 1:].ravel()], axis=1)
    down = np.stack([idx[:-1, :].ravel(), idx[1:, :].ravel()], axis=1)
    return np.concatenate([right, down]), rows * cols
