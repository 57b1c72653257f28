"""Seconds of set-up in the module bodies of ``bagua_tpu`` and
``bagua_tpu.trainer`` (the spans ``bagua_host/setup/import``), less the
programs compiled inside them; ``import jax`` is not the program's."""

from benchmark.setup_anatomy import seconds


def read(context):
    return seconds(context, "import")
