from repro.launch.cli import enable_compile_cache
from repro.sweep.cli import main

enable_compile_cache()
raise SystemExit(main())
