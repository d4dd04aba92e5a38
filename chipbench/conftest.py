"""Small versions of the traffic mixes that the test helpers' table
(``chipbench/tests/harness_util.py`` ``SMALL_TRAFFIC``) does not hold yet,
so that the harness, control and fault tests run every cell of
``workloads/`` at the reduced size on the CPU."""
import pytest

SMALL_TRAFFIC = {
    # the reduced hymba has a window of 8 and 4 meta tokens: prompts on
    # both sides of the window, every slot live at the crash
    "longcrash": {"prompt_tokens": {"14": 0.5, "22": 0.5},
                  "output_tokens": [6, 6], "crash": {"after_tokens": 3},
                  "clients": 4},
}


@pytest.fixture(autouse=True, scope="session")
def _small_traffic():
    from chipbench.tests import harness_util

    for name, mix in SMALL_TRAFFIC.items():
        harness_util.SMALL_TRAFFIC.setdefault(name, mix)
