"""Build and execute an example notebook from a cell-spec module.

The reference ships its tutorial workflows as committed, executed
notebooks (``/root/reference/example/notebooks/*.ipynb``); this repo
does the same, but authors them as plain-python cell specs so diffs
review like code and regeneration is one command:

    python tools/make_notebook.py SPEC.py OUT.ipynb

``SPEC.py`` defines ``CELLS = [("md"|"code", source), ...]``; the specs
for the shipped notebooks live in ``examples/notebooks/specs/``. The
tool builds the notebook, executes it via :func:`execute` — a fresh CPU
kernel with the repo on ``PYTHONPATH`` and the output directory as cwd;
the CI gate in ``tests/unittest/test_examples.py`` calls the SAME
function, so regeneration and CI cannot drift — and writes the executed
notebook: committed outputs can never go stale against the API because
CI re-executes them.
"""
import os
import runpy
import sys

import nbclient
import nbformat


def build(cells):
    nb = nbformat.v4.new_notebook()
    nb.metadata["kernelspec"] = {
        "display_name": "Python 3", "language": "python", "name": "python3"}
    for kind, src in cells:
        src = src.strip("\n")
        if kind == "md":
            nb.cells.append(nbformat.v4.new_markdown_cell(src))
        else:
            nb.cells.append(nbformat.v4.new_code_cell(src))
    return nb


def execute(nb, workdir):
    env_keys = {"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)))}
    old = {k: os.environ.get(k) for k in env_keys}
    os.environ.update(env_keys)
    try:
        client = nbclient.NotebookClient(
            nb, timeout=600, kernel_name="python3",
            resources={"metadata": {"path": workdir}})
        client.execute()
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return nb


def main(spec_path, out_path):
    cells = runpy.run_path(spec_path)["CELLS"]
    nb = build(cells)
    execute(nb, os.path.dirname(os.path.abspath(out_path)))
    nbformat.write(nb, out_path)
    print("wrote", out_path, "(%d cells, executed)" % len(nb.cells))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
