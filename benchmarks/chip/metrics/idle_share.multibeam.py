"""``idle_share.pulsar``'s reading, in the multibeam cell: the idle
share averaged over the chips."""
import runpy
from pathlib import Path

read = runpy.run_path(
    str(Path(__file__).with_name("idle_share.pulsar.py")))["read"]
