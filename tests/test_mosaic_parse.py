"""Unit tests for the Mosaic-dump parser's region tracking (fast tier).

The byte-level traffic assertions live in test_traffic_accounting.py (slow,
subprocess jax.export); these pin the pure-text parsing rules the whole
accounting rests on: string-literal braces must not skew region depth, and
a drifted stack must refuse instead of silently mis-attributing DMAs
(ADVICE r5 #1)."""

import pytest

from stencil_tpu.utils import mosaic_traffic as mt

_DMA_LINE = (
    '      tpu.enqueue_dma source(%0 : memref<2x8x128xf32, '
    "#tpu.memory_space<any>>) target(%1 : memref<2x8x128xf32, "
    "#tpu.memory_space<vmem>>) target_semaphore(%2)"
)


def _dump(body: str) -> str:
    return mt._MARKER + "/tmp/foo.py:12:\n" + body


def test_string_literal_braces_do_not_skew_depth():
    # the sym_name attr contains an unbalanced '{' inside a string literal;
    # the DMA after it is at top level, NOT inside a region
    body = "\n".join(
        [
            "module @kernel {",
            '  func.func @main() attributes {sym_name = "weird{name"} {',
            _DMA_LINE,
            "  }",
            "}",
        ]
    )
    (k,) = mt.parse_mosaic_dumps(_dump(body))
    assert len(k.dmas) == 1
    assert k.dmas[0].if_depth == 0 and k.dmas[0].loop_depth == 0


def test_scf_if_attribution_still_counts():
    body = "\n".join(
        [
            "module @kernel {",
            "  scf.if %cond {",
            _DMA_LINE,
            "  }",
            _DMA_LINE,
            "}",
        ]
    )
    (k,) = mt.parse_mosaic_dumps(_dump(body))
    assert [d.if_depth for d in k.dmas] == [1, 0]


def test_trailing_text_after_module_close_is_ignored():
    body = "\n".join(
        [
            "module @kernel {",
            _DMA_LINE,
            "}",
            "some later debug output with a stray { brace",
        ]
    )
    (k,) = mt.parse_mosaic_dumps(_dump(body))
    assert len(k.dmas) == 1


_WAIT_LINE = (
    "      tpu.wait_dma2 semaphore(%3 : memref<!tpu.dma_semaphore, "
    "#tpu.memory_space<semaphore_mem>>) src(%0 : memref<2x8x128xf32, "
    "#tpu.memory_space<any>>) dst(%1 : memref<2x8x128xf32, "
    "#tpu.memory_space<vmem>>)"
)


def test_events_keep_body_order_and_the_enclosing_ifs():
    # starts and waits in order, each with the ordinals of the scf.if ops
    # round it; schedule() picks a grid step's by the branches it takes
    body = "\n".join(
        [
            "module @kernel {",
            "  func.func @main(%arg0: i32, %arg1: memref<4x8x128xf32, "
            "#tpu.memory_space<any>>, %arg2: memref<2x8x128xf32, "
            "#tpu.memory_space<vmem>>, %arg3: memref<2x!tpu.dma_semaphore, "
            "#tpu.memory_space<semaphore_mem>>) attributes {a = [{}]} {",
            "  scf.if %first {",
            _DMA_LINE,
            "  }",
            "  scf.if %more {",
            "    scf.if %inner {",
            _WAIT_LINE,
            "    }",
            _DMA_LINE,
            "  } else {",
            _DMA_LINE,
            "  }",
            _WAIT_LINE,
            "  }",
            "}",
        ]
    )
    (k,) = mt.parse_mosaic_dumps(_dump(body))
    assert [(e.kind, e.branch) for e in k.events] == [
        ("start", (0,)), ("wait", (1, 2)), ("start", (1,)), ("start", (1,)),
        ("wait", ())]
    assert all(e.op.is_input and e.op.nbytes == 2 * 8 * 128 * 4 for e in k.events)
    assert [e.kind for e in k.schedule([1])] == ["start", "start", "wait"]
    assert [e.kind for e in k.schedule([1, 2])] == ["wait", "start", "start", "wait"]
    assert len(k.dmas) == 3 and k.vmem_bytes == 2 * 8 * 128 * 4


_DMA_GENERIC_LINE = (
    '      "tpu.enqueue_dma"(%129, %130, %132) <{operandSegmentSizes = '
    "array<i32: 1, 0, 1, 1, 0, 0>}> : (memref<1x144x384xf32, "
    "#tpu.memory_space<any>>, memref<1x144x384xf32, "
    "#tpu.memory_space<vmem>>, memref<!tpu.dma_semaphore, "
    "#tpu.memory_space<semaphore_mem>>) -> ()"
)


def test_generic_form_dma_parses():
    # older Mosaic prints ops in generic MLIR form; direction and extents
    # come from the trailing type signature (source first, target second)
    body = "\n".join(["module @kernel {", _DMA_GENERIC_LINE, "}"])
    (k,) = mt.parse_mosaic_dumps(mt._MARKER + "/tmp/foo.py:12:\n" + body)
    (d,) = k.dmas
    assert d.is_input and d.shape == (1, 144, 384) and d.nbytes == 221184


def test_unbalanced_module_raises():
    body = "\n".join(["module @kernel {", "  scf.if %cond {", _DMA_LINE])
    with pytest.raises(ValueError, match="unbalanced"):
        mt.parse_mosaic_dumps(_dump(body))


def test_overclosed_module_raises():
    # two closes on one line against a depth-1 stack: refuse loudly
    body = "\n".join(["module @kernel {", "} }"])
    with pytest.raises(ValueError, match="closes against"):
        mt.parse_mosaic_dumps(_dump(body))


def test_capture_traffic_rejects_reentry(monkeypatch):
    monkeypatch.setattr(mt, "_capture_active", True)
    with pytest.raises(RuntimeError, match="not reentrant"):
        mt.capture_traffic(lambda: (None, ()))
