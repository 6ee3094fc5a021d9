"""Profiling utilities: xplane wire-format reader + MFU math."""

import os
import struct

import numpy as np
import pytest

from tensor2robot_tpu.utils import profiling, xplane


def _varint(value: int) -> bytes:
  out = b""
  while True:
    bits = value & 0x7F
    value >>= 7
    if value:
      out += bytes([bits | 0x80])
    else:
      return out + bytes([bits])


def _field(number: int, wire: int, payload: bytes) -> bytes:
  return _varint((number << 3) | wire) + (
      _varint(int.from_bytes(payload, "little")) if wire == 0
      else _varint(len(payload)) + payload)


def _varint_field(number: int, value: int) -> bytes:
  return _varint((number << 3) | 0) + _varint(value)


def _msg_field(number: int, payload: bytes) -> bytes:
  return _varint((number << 3) | 2) + _varint(len(payload)) + payload


class TestXplaneReader:

  def test_parses_synthetic_trace(self, tmp_path):
    """Hand-encode an XSpace with one TPU plane, two ops, two events
    each — the reader must aggregate durations by op name."""
    # XEventMetadata {id=1, name=2}; map entry {key=1, value=2}.
    def event_metadata(meta_id, name):
      inner = (_varint_field(1, meta_id)
               + _msg_field(2, name.encode()))
      return _msg_field(4, _varint_field(1, meta_id)
                        + _msg_field(2, inner))

    # XEvent {metadata_id=1, duration_ps=3}.
    def event(meta_id, duration_ps):
      return _msg_field(4, _varint_field(1, meta_id)
                        + _varint_field(3, duration_ps))

    line = _msg_field(3, event(1, 2_000_000) + event(1, 3_000_000)
                      + event(2, 500_000))
    plane = (_msg_field(2, b"/device:TPU:0")
             + line
             + event_metadata(1, "%fusion.1")
             + event_metadata(2, "%copy.9"))
    host_plane = (_msg_field(2, b"/host:CPU")
                  + _msg_field(3, event(1, 9_000_000))
                  + event_metadata(1, "python"))
    xspace = _msg_field(1, plane) + _msg_field(1, host_plane)

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace)
    totals = xplane.op_times_ms(str(tmp_path))
    assert totals == {"%fusion.1": 0.005, "%copy.9": 0.0005}
    top = xplane.top_ops(str(tmp_path), k=1)
    assert top == [("%fusion.1", 0.005)]

  def test_empty_dir(self, tmp_path):
    assert xplane.op_times_ms(str(tmp_path)) == {}


class TestMFU:

  def test_known_device_peak(self, monkeypatch):
    class FakeDevice:
      platform = "tpu"
      device_kind = "TPU v5 lite"
    # The override is a CPU-test device: it never replaces a TPU peak.
    monkeypatch.setenv("T2R_PEAK_FLOPS_OVERRIDE", "1e12")
    assert profiling.device_peak_flops(FakeDevice()) == 197e12
    assert profiling.mfu(100.0, 197e8, FakeDevice()) == 0.01

  def test_unknown_tpu_kind_raises(self, monkeypatch):
    class FakeDevice:
      platform = "tpu"
      device_kind = "TPU mystery"
    monkeypatch.setenv("T2R_PEAK_FLOPS_OVERRIDE", "1e12")
    with pytest.raises(ValueError, match="TPU mystery"):
      profiling.device_peak_flops(FakeDevice())

  def test_cpu_has_no_peak_unless_overridden(self, monkeypatch):
    monkeypatch.delenv("T2R_PEAK_FLOPS_OVERRIDE", raising=False)
    assert profiling.device_peak_flops() is None
    assert profiling.mfu(1.0, 1.0) is None
    monkeypatch.setenv("T2R_PEAK_FLOPS_OVERRIDE", "2e12")
    assert profiling.device_peak_flops() == 2e12
