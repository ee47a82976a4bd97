"""``copy_bytes_x.batch``'s reading, in the pulsar cell."""
import runpy
from pathlib import Path

read = runpy.run_path(
    str(Path(__file__).with_name("copy_bytes_x.batch.py")))["read"]
