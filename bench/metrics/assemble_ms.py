"""assemble_ms (server): host time per served batch spent stacking the
requests and putting them on the device, the ``repro.server.assemble``
span's counter ``SearchServer.stats["assemble_s"]``."""

from benchlib import program


def read(run):
    return program.server_ms(run, "assemble_s")
