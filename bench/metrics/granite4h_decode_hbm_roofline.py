"""``decode_hbm_roofline`` on the hybrid cell's counts: ``drivers/serve_hybrid.py`` sets
``R.decode_step_bytes`` from ``lib.flops_hybrid.decode_step_bytes``, every
held weight a token reads, the experts uniform routing reaches, the SSM
state and conv tails read and written, and the K/V cache."""
import importlib.util
import os

_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "decode_hbm_roofline.py")
_spec = importlib.util.spec_from_file_location("bench_metric_decode_hbm_roofline_for_granite4h",
                                               _path)
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
read = _base.read
