from . import compile_cache, flags, native, paths
from .compile_cache import enable_compile_cache
from .native import NativeLoader, native_available
from .paths import get_data_path, get_logs_path

__all__ = ["compile_cache", "flags", "native", "paths",
           "enable_compile_cache", "NativeLoader", "native_available",
           "get_data_path", "get_logs_path"]
