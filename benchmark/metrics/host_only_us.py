"""host_only_us: the time in which some bench.request span was open and
no device operation ran, per request completed in the traced stretch.  Read for every
``host_only_us.<group>``."""

from tracereduce import run_host_only_us as read  # noqa: F401
