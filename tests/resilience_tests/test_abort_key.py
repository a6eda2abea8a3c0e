"""The abort poison key must be readable WITHOUT blocking
(``key_value_try_get``). A probe that cannot see the key silently
disables the watchdog's whole bounded-abort contract, so the read path
is pinned here."""

from chainermn_tpu.comm.object_plane import (
    _ABORT_FLAG,
    _ABORT_KEY,
    _read_abort,
)


class TryGetClient:
    """Non-blocking point read, raises on a missing key — the installed
    jaxlib client's contract."""

    def __init__(self):
        self.kv = {}

    def key_value_try_get(self, key):
        if key in self.kv:
            return self.kv[key]
        raise KeyError(key)


def test_flag_lives_under_the_abort_directory():
    assert _ABORT_FLAG.startswith(_ABORT_KEY + "/")


def test_try_get_client_reads_abort():
    client = TryGetClient()
    assert _read_abort(client) is None
    client.kv[_ABORT_FLAG] = "peer 1 died"
    assert _read_abort(client) == "peer 1 died"


def test_read_abort_ignores_unrelated_keys():
    client = TryGetClient()
    client.kv["og/abortive/other"] = "not an abort"
    client.kv["og/liveness/seed"] = "1"
    assert _read_abort(client) is None


def test_read_abort_swallows_client_errors():
    class BrokenClient:
        def key_value_try_get(self, key):
            raise RuntimeError("coordinator gone")

    assert _read_abort(BrokenClient()) is None


def test_installed_client_has_try_get():
    # the one branch kept is the one the installed jaxlib takes
    from jax._src.lib import _jax

    assert hasattr(_jax.DistributedRuntimeClient, "key_value_try_get")
