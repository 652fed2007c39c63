"""answer_wait_ms (server): host time per served batch from the search
function's return to the answers on the host (device completion and the
copy back), the ``repro.server.wait`` span's counter
``SearchServer.stats["wait_s"]``."""

from benchlib import program


def read(run):
    return program.server_ms(run, "wait_s")
