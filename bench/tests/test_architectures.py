"""The dense GQA architecture module builds the weights the harness built
before it was split out, bit for bit."""
import hashlib

import jax
import numpy as np
import pytest
from conftest import TINY_CONFIG

from harness import spec

# sha256 of every array of ``build(seed)`` at TINY_CONFIG's shapes (path,
# dtype, shape and bytes of each leaf, in path order), as the harness's
# weights module gave them before the architectures were split out
DIGESTS = {
    0: "2d77eed4a7794d692e202920a4e1e3134d37b4631f440fa343f2f206e60f233a",
    4294967311:
        "33f5cd538615d218aeb5aa7e315cd734f8cd604dc8aec54600ddc8e1a020dcc4",
}


def digest(weights) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(weights)[0]
    for path, leaf in sorted(leaves,
                             key=lambda t: jax.tree_util.keystr(t[0])):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype),
                     str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed", sorted(DIGESTS))
def test_dense_weights_are_unchanged(tiny, seed):
    cell = spec.load_cell("tiny.small", tiny, tiny / "bench")
    arch = spec.architecture(cell)
    assert cell.config["reference"] == TINY_CONFIG["reference"]
    assert digest(arch.build(seed, arch.Shapes.of(cell.config))) == \
        DIGESTS[seed]
