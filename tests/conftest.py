"""Test session config. NOTE: no XLA device-count flags here — smoke tests
and benches must see exactly one CPU device (the 512-device flag belongs to
extras/dryrun.py alone). Multi-device tests spawn subprocesses, whose
scripts set the same fusion-emitter flag as below."""
import os
import sys
import pathlib

# XLA:CPU's fusion emitters compile the sort-merge join in time that grows
# ~3.5x per doubling of its build side (2.4 s at 1,024 rows, 33 s at 4,096,
# minutes past that); the older emitters take under a second at any size
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_cpu_use_fusion_emitters=false"
).strip()

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
