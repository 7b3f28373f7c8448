"""The serve-smoke driver keeps its own process off jax.

On an accelerator a chip belongs to one process: the server child holds it,
so the driver must compute its offline reference streams in a child of its
own that exits first, and never import jax itself.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from repro.configs import get_config
from repro.launch import serve_smoke_client as ssc

_PROG = textwrap.dedent("""
    import json, sys
    from repro.configs import get_config
    from repro.launch import serve_smoke_client as ssc
    reqs = ssc.workload(get_config("tiny-relu").vocab_size)
    ref = ssc.reference_in_child("plain", reqs)
    assert "jax" not in sys.modules, "the driver process imported jax"
    print(json.dumps(ref))
""")


def test_reference_streams_run_in_a_child_and_parent_never_imports_jax():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, ["src", env.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, "-c", _PROG], capture_output=True,
                       text=True, env=env, timeout=400)
    assert r.returncode == 0, r.stderr[-3000:]
    got = {int(i): t for i, t in
           json.loads(r.stdout.strip().splitlines()[-1]).items()}
    reqs = ssc.workload(get_config("tiny-relu").vocab_size)
    greedy = [i for i, q in enumerate(reqs) if "temperature" not in q]
    assert sorted(got) == greedy
    for i in greedy:
        assert len(got[i]) == reqs[i]["max_new"]
    # the child's streams are the in-process reference, token for token
    assert got == ssc.reference_streams("plain", reqs)
