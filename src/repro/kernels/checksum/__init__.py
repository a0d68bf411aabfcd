from .checksum import (ACCUMULATOR_DTYPES, QAChecksumAccumulator, QAStats,
                       StreamSizeError, device_checksum, qa_checksum,
                       qa_checksum_batched, qa_stats)
from .ref import (device_checksum_ref, qa_checksum_ref,
                  qa_checksum_batched_ref)

__all__ = ["ACCUMULATOR_DTYPES", "QAChecksumAccumulator", "QAStats",
           "StreamSizeError", "device_checksum", "device_checksum_ref",
           "qa_checksum", "qa_checksum_ref", "qa_checksum_batched",
           "qa_checksum_batched_ref", "qa_stats"]
