"""``decode_hbm_roofline`` on the MLA driver's counts: the driver sets
``R.decode_step_bytes`` from ``lib.flops_mla.decode_step_bytes``, every
held non-routed weight, the experts uniform routing reaches, and the latent
cache."""
import importlib.util
import os

_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "decode_hbm_roofline.py")
_spec = importlib.util.spec_from_file_location("bench_metric_decode_hbm_roofline_for_dsv2",
                                               _path)
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
read = _base.read
