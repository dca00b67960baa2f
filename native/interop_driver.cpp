// Native XLA interop driver — C++ executing XLA computations on shared
// buffers, both directions proven with asserts (C10 completion).
//
// The reference's distinctive interop achievement is two RUNTIMES
// sharing one device context: an OpenMP-allocated buffer read by a SYCL
// kernel, and a SYCL-allocated buffer read by an OpenMP kernel, each
// validated elementwise (sycl_omp_ze_interopt/interop_omp_ze_sycl.cpp:
// 81-101). Here the two runtimes are THIS C++ program (which owns
// main(), the allocator, and every assert) and the XLA runtime (hosted
// in an embedded CPython — the binding layer, playing the role the OMP
// interop API plays in the reference: the vehicle for obtaining the
// other runtime's context, not the thing under test).
//
//   Leg 1 (native alloc -> XLA compute; ≙ :81-91): C++ aligned_alloc's
//     a 128-aligned buffer and fills it; XLA dlpack-imports it with
//     ZERO COPY (pointer identity asserted on both sides: the XLA
//     array's device pointer IS the C allocation) and reduces it; C++
//     asserts the reduction against its own double-precision oracle.
//     Alignment is load-bearing: XLA aliases only >=64-byte-aligned
//     imports (the reference's ALIGNMENT constant in TPU-stack form,
//     allreduce-mpi-sycl.cpp:19-21).
//
//   Leg 2 (XLA alloc -> native read, in place; ≙ :93-101): XLA
//     allocates a buffer; C++ reads the raw device memory DIRECTLY
//     (no export, no copy) and validates the fill; XLA then runs a
//     DONATED computation that writes its output into that same buffer
//     (input_output aliasing); C++ re-reads the SAME address and
//     validates the new values — native code watching XLA mutate
//     memory in place.
//
// Mailbox protocol: a C++-owned double[16] whose address is given to
// the embedded interpreter — even the control channel is shared memory.
//   [0] leg-1 zero-copy flag   [1] leg-1 XLA checksum
//   [2] leg-2 buffer address   [3] leg-2 stage flag
//   [4] leg-2 alias flag       [15] python-side fatal-error flag
//
// Usage: interop_driver [--elements N] [--pythonpath A:B:C]
// Exit 0 iff every assert on both sides holds (prints SUCCESS).

#include <Python.h>

#include <cmath>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>

namespace {

double* g_mail = nullptr;

bool run_py(const char* code) {
  if (PyRun_SimpleString(code) != 0) {
    std::fprintf(stderr, "interop_driver: python stage failed\n");
    return false;
  }
  if (g_mail && g_mail[15] != 0.0) {
    std::fprintf(stderr, "interop_driver: python-side assert failed\n");
    return false;
  }
  return true;
}

}  // namespace

int main(int argc, char** argv) {
  long n = 1 << 16;
  std::string pythonpath;
  for (int i = 1; i < argc; ++i) {
    if (std::strcmp(argv[i], "--elements") == 0 && i + 1 < argc) {
      n = std::atol(argv[++i]);
    } else if (std::strcmp(argv[i], "--pythonpath") == 0 && i + 1 < argc) {
      pythonpath = argv[++i];
    } else if (std::strcmp(argv[i], "--help") == 0) {
      std::printf("usage: interop_driver [--elements N] [--pythonpath A:B]\n");
      return 0;
    }
  }
  if (n <= 0) {
    std::fprintf(stderr, "interop_driver: bad --elements\n");
    return 2;
  }

  // the embedded XLA must be the host CPU runtime (same memory space as
  // this process — zero-copy is a same-address-space property), never
  // the chip: a parent interop_app may hold it, and one process owns it
  setenv("JAX_PLATFORMS", "cpu", 1);
  setenv("JAX_ENABLE_X64", "1", 1);  // exact f64 checksum at any size
  if (!pythonpath.empty()) setenv("PYTHONPATH", pythonpath.c_str(), 1);

  // ---- native allocation (leg 1), before any Python exists
  // round the byte size up to a multiple of the alignment: C11 permits
  // aligned_alloc to fail otherwise (e.g. -n 1000 -> 4000 bytes)
  size_t bytes = ((n * sizeof(float) + 127) / 128) * 128;
  float* buf = static_cast<float*>(aligned_alloc(128, bytes));
  double mail[16] = {0};
  g_mail = mail;
  if (!buf) {
    std::fprintf(stderr, "interop_driver: aligned_alloc failed\n");
    return 2;
  }
  double want_sum = 0.0;
  for (long i = 0; i < n; ++i) {
    buf[i] = 0.5f * static_cast<float>(i % 1024);
    want_sum += buf[i];
  }

  Py_Initialize();
  char setup[2048];
  std::snprintf(setup, sizeof(setup),
                "import ctypes, struct, numpy as np\n"
                "import jax, jax.numpy as jnp\n"
                "N = %ld\n"
                "BUF = 0x%llx\n"
                "mail = (ctypes.c_double * 16).from_address(0x%llx)\n"
                "assert jax.devices()[0].platform == 'cpu'\n",
                n, static_cast<unsigned long long>(
                       reinterpret_cast<uintptr_t>(buf)),
                static_cast<unsigned long long>(
                    reinterpret_cast<uintptr_t>(mail)));
  if (!run_py(setup)) return 1;

  // ---- leg 1: XLA reads C++-owned memory, zero copy
  if (!run_py(
          "try:\n"
          "    x = np.ctypeslib.as_array((ctypes.c_float * N)"
          ".from_address(BUF))\n"
          "    arr = jax.dlpack.from_dlpack(x)\n"
          "    ptr = arr.addressable_shards[0].data"
          ".unsafe_buffer_pointer()\n"
          "    mail[0] = 1.0 if ptr == BUF else 0.0\n"
          "    mail[1] = float(jnp.sum(arr.astype(jnp.float64)))\n"
          "except Exception as e:\n"
          "    print('leg1 error:', e)\n"
          "    mail[15] = 1.0\n"))
    return 1;
  if (mail[0] != 1.0) {
    std::fprintf(stderr, "FAILURE: leg1 import copied (no aliasing)\n");
    return 1;
  }
  if (std::fabs(mail[1] - want_sum) > 1e-6 * std::fabs(want_sum)) {
    std::fprintf(stderr, "FAILURE: leg1 checksum %f != %f\n", mail[1],
                 want_sum);
    return 1;
  }
  std::printf("interop_driver leg1 OK: XLA read %ld natively-owned "
              "floats in place (sum %.1f)\n", n, mail[1]);

  // ---- leg 2 stage A: XLA allocates + fills; C++ reads it raw
  if (!run_py(
          "try:\n"
          "    a = jnp.full((N,), 2.0, jnp.float32)\n"
          "    jax.block_until_ready(a)\n"
          "    leg2_ptr = a.addressable_shards[0].data"
          ".unsafe_buffer_pointer()\n"
          // the address crosses the mailbox as its exact uint64 BIT
          // pattern (a double-rounded address >= 2^53 would lose low
          // bits and turn the native re-read into a wild dereference)
          "    mail[2] = struct.unpack('<d', struct.pack('<Q',"
          " leg2_ptr))[0]\n"
          "    mail[3] = 1.0\n"
          "except Exception as e:\n"
          "    print('leg2a error:', e)\n"
          "    mail[15] = 1.0\n"))
    return 1;
  uint64_t leg2_bits = 0;
  std::memcpy(&leg2_bits, &mail[2], sizeof(leg2_bits));
  const float* xla_mem =
      reinterpret_cast<const float*>(static_cast<uintptr_t>(leg2_bits));
  for (long i = 0; i < n; ++i) {
    if (xla_mem[i] != 2.0f) {
      std::fprintf(stderr, "FAILURE: leg2 pre-read [%ld]=%f != 2\n", i,
                   xla_mem[i]);
      return 1;
    }
  }

  // ---- leg 2 stage B: XLA writes IN PLACE (donation); C++ re-reads
  if (!run_py(
          "try:\n"
          "    out = jax.jit(lambda v: v * 3 + 1, donate_argnums=0)(a)\n"
          "    jax.block_until_ready(out)\n"
          "    optr = out.addressable_shards[0].data"
          ".unsafe_buffer_pointer()\n"
          "    mail[4] = 1.0 if optr == leg2_ptr else 0.0\n"
          "except Exception as e:\n"
          "    print('leg2b error:', e)\n"
          "    mail[15] = 1.0\n"))
    return 1;
  if (mail[4] != 1.0) {
    std::fprintf(stderr, "FAILURE: leg2 donation did not alias\n");
    return 1;
  }
  for (long i = 0; i < n; ++i) {
    if (xla_mem[i] != 7.0f) {
      std::fprintf(stderr, "FAILURE: leg2 post-read [%ld]=%f != 7\n", i,
                   xla_mem[i]);
      return 1;
    }
  }
  std::printf("interop_driver leg2 OK: XLA wrote %ld floats in place; "
              "native re-read validated\n", n);

  Py_Finalize();
  free(buf);
  std::printf("SUCCESS\n");
  return 0;
}
