"""`python -m dragonfly2_tpu.colocated` — scheduler and trainer in one
process, for a host with one chip."""

import sys

from dragonfly2_tpu.cli.runner import main_with_config
from dragonfly2_tpu.colocated.server import build

if __name__ == "__main__":
    sys.exit(main_with_config("colocated", build))
