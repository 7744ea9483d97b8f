#!/usr/bin/env python
"""Is a cell's compiled step still the same program? (no chip needed)

    JAX_PLATFORMS=cpu python tools/step_hash.py <cell> [<cell> ...] [--out DIR]

Compiles each cell's energy-and-forces step at the cell's own size for a
described TPU v5e (``tests/benchmark/test_compile_v5e.compile_step``),
normalizes the compiled text and prints its line count and sha256: run it
on two trees and compare. A refactor that must not move a number shows
equal hashes before it spends chip time (PRs 26, 29, 30). Normalized away,
because they differ between trees that compile the same instructions:

- ``metadata={...}`` and the file tables (source lines move);
- the numbers in instruction names (``fusion.1863``, and every numeric
  segment of ``constant.3997.clone.34``): each name becomes its kind plus
  the order of its first appearance;
- the location table inside a Mosaic kernel's serialized MLIR, which
  holds the Python call stack and the checkout's path: each kernel is
  parsed and printed without debug info, and that text is hashed in place
  of the bytes.

``--out DIR`` keeps the normalized text (``<cell>.txt``) for ``diff``.
A MACE cell compiles in 3 to 5 minutes, ``tensornet-md-1c`` and
``uma-md-1c`` in about one.
"""

import argparse
import base64
import hashlib
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
METADATA = re.compile(r",?\s*metadata=\{[^{}]*\}")
KERNEL_BODY = re.compile(r'"body":\s*"([^"]+)"')
NAME = re.compile(
    r"(?<![\w.\-])%?([A-Za-z_][A-Za-z0-9_\-]*(?:\.[A-Za-z0-9_\-]+)+)(?![\w.])")


def kernel_text(body: str) -> str:
    """A serialized Mosaic kernel, printed without its locations."""
    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def normalize(text: str) -> str:
    lines, in_table = [], False
    for line in text.splitlines():
        head = line.strip()
        if head.startswith(TABLES):
            in_table = True
            continue
        if in_table and (not head or head[0].isdigit()):
            continue
        in_table = False
        line = METADATA.sub("", line)
        if 'custom_call_target="tpu_custom_call"' in line:
            line = KERNEL_BODY.sub(
                lambda m: '"body":"%s"' % hashlib.sha256(
                    kernel_text(m.group(1)).encode()).hexdigest()[:16], line)
        lines.append(line)
    seen, counts = {}, {}

    def renumber(m):
        name = m.group(1)
        parts = name.split(".")
        if not any(p.isdigit() for p in parts[1:]):
            return m.group(0)
        if name not in seen:
            kind = ".".join(p for p in parts if not p.isdigit())
            counts[kind] = counts.get(kind, 0) + 1
            seen[name] = f"{kind}#{counts[kind]}"
        return seen[name]

    return NAME.sub(renumber, "\n".join(lines))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("cells", nargs="+")
    parser.add_argument("--out")
    args = parser.parse_args(argv)

    import jax
    import pytest
    from jax.experimental import topologies

    from benchmark.harness import spec
    from tests.benchmark.test_compile_v5e import compile_step

    # written for a described chip, such an entry cannot be read back
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in args.cells:
        patch = pytest.MonkeyPatch()
        try:
            compiled = compile_step(spec.load_cell(name), topo, patch)
        finally:
            patch.undo()
        text = normalize(compiled.as_text())
        memory = compiled.memory_analysis()
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, f"{name}.txt"), "w") as f:
                f.write(text)
        print(f"{name}: lines {text.count(chr(10)) + 1} sha256 "
              f"{hashlib.sha256(text.encode()).hexdigest()[:16]} arguments "
              f"{memory.argument_size_in_bytes / 1e9:.3f} GB temporaries "
              f"{memory.temp_size_in_bytes / 1e9:.3f} GB", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
