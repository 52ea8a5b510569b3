"""Host time per round in the harness's ``sync`` span: once the chunk is
done, pulling its RoundStream to the host and decoding it with the
program's ``_unpack_stream``.  The device waits for the host in it."""


def read(run):
    return 1e3 * run.sync_s / run.rounds
