"""Seconds of set-up in ``init_process_group``, ``Trainer.__init__`` and
``Trainer.init_state`` (the spans ``bagua_host/setup/group``, ``/trainer``,
``/init_state``), less the programs compiled inside them."""

from benchmark.setup_anatomy import seconds


def read(context):
    return seconds(context, "init")
