"""The one-chip cluster: scheduler and trainer in one process.

A chip belongs to one process at a time, and both binaries own device
planes (the fits; the scoring service and the topology engine). On a
host with one chip the one-host cluster (the reference's scheduler dials
its trainer on loopback by default; this repo's compose file starts
manager, scheduler, trainer, seed peer and peer on one host) therefore
cannot start its ``trainer`` and ``scheduler`` containers side by side.
This service is the two of them in one process on one backend.
"""

from dragonfly2_tpu.colocated.server import (  # noqa: F401
    ColocatedConfig,
    ColocatedServer,
    build,
)
