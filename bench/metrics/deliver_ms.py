"""deliver_ms (server): host time per served batch for the bookkeeping and
the per-request responses, the ``repro.server.deliver`` span's counter
``SearchServer.stats["deliver_s"]``."""

from benchlib import program


def read(run):
    return program.server_ms(run, "deliver_s")
