"""``python -m bifromq_tpu --config conf.yml`` — standalone broker CLI."""

from .utils.jaxenv import setup_compile_cache

setup_compile_cache()

from .starter import main  # noqa: E402

main()
