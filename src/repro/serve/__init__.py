from .engine import Metrics, PagedEngine, Request  # noqa: F401
from .kvcache import PagedKVCache  # noqa: F401
from .load import LoadSpec, drive, generate  # noqa: F401
from .scheduler import Plan, Scheduler, ServeConfig  # noqa: F401
