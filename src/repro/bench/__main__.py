import sys

from repro.bench.cli import main
from repro.launch.compile_cache import enable_compile_cache

enable_compile_cache()

sys.exit(main())
