"""The result's device record: the peak memory of the fullest of the
cell's chips, and each one's beside it."""
from dataclasses import dataclass
from typing import Optional

import run


@dataclass
class Device:
    peak: Optional[int]
    platform: str = "tpu"
    device_kind: str = "TPU v5 lite"

    def memory_stats(self):
        return None if self.peak is None else {"peak_bytes_in_use": self.peak}


def test_memory_peak_is_the_fullest_of_the_cells_chips():
    devices = [Device(5), Device(9), Device(7), Device(3)]
    rec = run.device_record(devices, 4)
    assert rec == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
                   "memory_peak_bytes": 9,
                   "memory_peak_bytes_by_device": [5, 9, 7, 3]}
    # a one-chip cell reads its own chip only
    assert run.device_record(devices, 1)["memory_peak_bytes"] == 5


def test_a_device_that_reports_no_memory_gives_none():
    assert run.device_record([Device(None)], 1)["memory_peak_bytes"] is None
