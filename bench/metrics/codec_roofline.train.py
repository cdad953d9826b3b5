"""The gradient codec's share of its HBM roofline, in %.

Least bytes of the codec's interface: read the N-element float32 gradient
message and write N float32 values back, 8 N bytes, whatever implements
it, where N is the family's ``n_params`` (``bench/models/<family>.py``).
Divided by the HBM peak, that is the least time; over the device time of
every operation under the codec per step (``codec_ms_per_step``)."""
import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "bench_metric_codec_time", Path(__file__).with_name(
        "codec_ms_per_step.train.py"))
_codec = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_codec)


def interface_bytes(family, cfg: dict) -> int:
    return 8 * family.n_params(cfg)


def read(r):
    s = _codec.codec_seconds_per_step(r)
    if s is None:
        return None
    return 100.0 * interface_bytes(r.family, r.config) \
        / r.peaks["hbm_bytes_per_s"] / s
