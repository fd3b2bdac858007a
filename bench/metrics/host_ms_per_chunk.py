"""Host time inside each ``RoundEngine.run_device`` call of the window
(the harness's ``dispatch`` span: argument handling, dispatch and
donation; the device runs on), in ms per chunk, by the host clock."""


def read(facts):
    return 1e3 * sum(facts.host_chunk_s) / len(facts.host_chunk_s)
