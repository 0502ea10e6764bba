"""Test session config.

8 virtual CPU devices so distributed/pipeline tests can build small meshes.
(Deliberately NOT 512 — the production-mesh device count is set only inside
launch/dryrun.py, which owns its own process.)  ``jax_num_cpu_devices`` must
be set before the CPU backend initializes, so this runs at conftest import
time, before any test module touches devices.
"""
import jax

_N_DEVICES = 8

jax.config.update("jax_num_cpu_devices", _N_DEVICES)
