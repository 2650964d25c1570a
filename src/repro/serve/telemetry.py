"""The process's span recorder, ``repro/telemetry.py``, under the name
the benchmark's readers import (``perfbench/telemetry.py``)."""

from repro.telemetry import *  # noqa: F401,F403
