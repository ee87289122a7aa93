"""The yardstick's own static tests, as tier-1 cases: ``BENCHMARK.json``
against the contract's rules and the files each entry names
(``benchmark/tests/test_contract.py``), the useful-work arithmetic
(``test_flops.py``), the statistics (``test_stats.py``), the traffic
generator (``test_traffic.py``), the latent configuration's counts and
reference contract (``test_latent_work.py``), the picking configuration's
(``test_sparse_latent_work.py``) and the split of the device's
idle time over the host's spans (``test_host_path.py``). 80-odd cases, three seconds, no subprocess
and no device, so a PR that breaks the harness's contract — an entry it
adds to ``BENCHMARK.json``, a configuration file that cuts a width — is
refused by the driver's own run of ``tests/``. The rest of
``benchmark/tests`` (rehearsals in subprocesses, two minutes) stays by
hand. The cases are the benchmark's functions themselves, collected here
under this module's name; no two of the files share a test's name
(``test_no_two_files_share_a_name``)."""

import importlib
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

FILES = ("test_contract", "test_flops", "test_stats", "test_traffic",
         "test_latent_work", "test_host_path", "test_sparse_latent_work",
         "test_setup_readers")
_collected = {}
for _file in FILES:
    _mod = importlib.import_module(f"benchmark.tests.{_file}")
    for _name in sorted(vars(_mod)):
        if _name.startswith("test_"):
            _collected.setdefault(_name, []).append(_file)
            globals()[_name] = getattr(_mod, _name)


def test_no_two_files_share_a_name():
    shared = {n: f for n, f in _collected.items() if len(f) > 1}
    assert not shared and len(_collected) >= 20
