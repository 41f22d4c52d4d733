"""The paged engine's programs for the model classes of the accepted serve
cells come out as pinned (``tools/program_identity.py``: StableHLO with
locations stripped, kernels in interpret mode). The pins are the programs
of PR 30, which PR 31 (a third class of cache beside them) had to leave as
they were, as PR 33 did (a looped model's two programs are pinned beside
them), and PR 37 (``DeepseekV3Attention`` takes its sizes as arguments:
kanana's two programs are byte for byte what they were; the new model's two
are pinned beside them). A change that means to alter one of them updates its pin here
and says so in CHANGES.md; one that does not has changed what
``mistral7b-decode-sat``, ``kanana2-docqa-decode`` or
``ouro-reason-decode`` runs."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PINNED = {
    "llama.block": "87f0666665c7e03e745246d9828ceef0daa0ce04",
    "llama.chunk": "db442fc2d52832c9bed72a9837dd5b2c6cbddd0f",
    "llama_int8.block": "a64744e0f925390575129e611b215b3085a9aa6a",
    "llama_int8.chunk": "315454823dde9af0dd1aac50adea43a61726c18c",
    "deepseek_v3.block": "a3c6cface75b212e43565830e8966dd03f9b533f",
    "deepseek_v3.chunk": "737a61e307860907e16b107ba441f39b755a2760",
    # PR 33: a looped model's two programs (the passes a scan in each)
    "ouro.block": "d3bf11f9184cf1aa62eee8e9e1495b7bfaee0f78",
    "ouro.chunk": "07b38559f2adab2faa4491cf7dcc852151f6073c",
    # PR 37: learned sparse attention over a latent cache through the hybrid
    # backend (the three kernels' bodies are in the text)
    "dots3_note.block": "bfbdb67f80ad2d06dc8c54bf04b4b54cc89b95df",
    "dots3_note.chunk": "3e8f4231eedd6cbfbe3b3e387e740fcd996cc219",
}


@pytest.fixture(scope="module")
def fingerprints():
    # a process of its own: what an earlier test traced never shapes the text
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "program_identity.py")],
        capture_output=True, text=True, timeout=600, check=True)
    return json.loads(out.stdout)


@pytest.mark.parametrize("program", sorted(PINNED))
def test_accepted_cells_programs_are_as_pinned(fingerprints, program):
    assert fingerprints[program] == PINNED[program]
