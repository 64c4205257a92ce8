"""Pass registration: importing this package registers every built-in
pass with the :mod:`..registry`."""

from . import aliasing  # noqa: F401
from . import donation  # noqa: F401
from . import error_paths  # noqa: F401
from . import fault_points  # noqa: F401
from . import host_sync  # noqa: F401
from . import metric_names  # noqa: F401
from . import recompile  # noqa: F401
from . import spmd_golden  # noqa: F401
