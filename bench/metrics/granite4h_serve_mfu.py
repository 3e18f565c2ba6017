"""``serve_mfu`` on the hybrid cell's counts: ``drivers/serve_hybrid.py`` sets
``R.wave_flops`` from ``lib.flops_hybrid.wave_flops``, the held share's
routed experts at top-k x held / router outputs a token and the SSD at its
recurrence's work."""
import importlib.util
import os

_path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "serve_mfu.py")
_spec = importlib.util.spec_from_file_location("bench_metric_serve_mfu_for_granite4h", _path)
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
read = _base.read
