"""Subprocess check: with four devices, the paged serving factory puts
one engine on each (a Cronus pair plus two workers), and every request's
token stream equals the run with all four engines on one device.

CPU rehearsal of ``chip_smoke.py --four-chips``: the same code, at smoke
widths, on four virtual host devices."""
import os
import sys
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4"
                           + " --xla_cpu_parallel_codegen_split_count=1")
ROOT = os.path.join(os.path.dirname(__file__), "..", "..")
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)
import jax
import chip_smoke
from repro.configs import get_config
from repro.models import build_model

assert len(jax.local_devices()) == 4, jax.local_devices()
model = build_model(get_config("qwen2-7b", smoke=True))
params = model.init_params(jax.random.PRNGKey(0), model.dtype,
                           device=jax.local_devices()[0])
runs = chip_smoke.four_engine_streams(model, params)
for name, run in runs.items():
    print(name, "engine devices", run["devices"])
assert runs["spread"]["devices"] == [0, 1, 2, 3], runs["spread"]["devices"]
assert runs["one_device"]["devices"] == [0, 0, 0, 0], runs["one_device"]
assert runs["spread"]["pair_devices"] == [0, 1], runs["spread"]
print("handed across devices", runs["spread"]["handed"])
assert runs["spread"]["handed"] > 0, "no PPI->CPI handoff across devices"
assert runs["spread"]["streams"] == runs["one_device"]["streams"]
print("OK", len(runs["spread"]["streams"]), "identical token streams")
