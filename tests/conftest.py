import os
import sys

# Tests see ONE device (the dry-run sets its own 512-device flag in a
# subprocess).  Keep threads bounded for the single-core container.
# excess precision off: XLA otherwise keeps bf16 elementwise chains at f32
# inside fusions, with fusion boundaries (and therefore rounding) depending
# on the surrounding computation shape — prefill (S tokens) and decode
# (1 token) then disagree by ~1 ulp/layer, which is exactly what the
# serving-consistency test must be able to rule out.  Flags are APPENDED
# to any user-set XLA_FLAGS (setdefault would silently drop them).
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=1"
if "--xla_allow_excess_precision" not in _flags:
    _flags += " --xla_allow_excess_precision=false"
os.environ["XLA_FLAGS"] = _flags.strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)
# no persistent compilation cache in the test run: nothing is written to
# the checkout's .jax_cache (or to JAX_COMPILATION_CACHE_DIR)
jax.config.update("jax_enable_compilation_cache", False)
