"""Plain reference of what ingest must commit for one accepted scan.

The BIDS file is the scan as float32 in ``.npy`` form, C order; its record
carries the sha256 of exactly those bytes, the QA fold over them
(``qa_fold``) and the transfer checksum ``(s2 << 32) | s1``; its sidecar
holds the dump's acquisition metadata. ``lowp=True`` is the control: the
volume stored in bfloat16 precision before it is written, one step below
the float32 the configuration states.
"""
from __future__ import annotations

import hashlib
import io
import os
import sys

import ml_dtypes
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)
from qa_fold import qa_fold  # noqa: E402

# Counts of scans that disagree: every limit is exact.
LIMITS = {"missing": 0, "records": 0, "files": 0, "fold_off_chip": 0}


def lower(vol: np.ndarray) -> np.ndarray:
    """The control's volume: float32 holding bfloat16's precision."""
    return np.asarray(vol, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)


def expected(vol: np.ndarray, lowp: bool = False) -> dict:
    f32 = np.ascontiguousarray(lower(vol) if lowp else vol,
                               dtype=np.float32)
    buf = io.BytesIO()
    np.save(buf, f32)
    data = buf.getvalue()
    qa = qa_fold(f32)
    return {"sha256": hashlib.sha256(data).hexdigest(), "nbytes": len(data),
            "qa": qa, "checksum": f"{(qa['s2'] << 32) | qa['s1']:016x}"}
