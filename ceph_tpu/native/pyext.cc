// CPython extension bindings for the native EC kernels.
//
// The ctypes path costs ~8-10us per call (pointer casts + foreign
// call setup) — more than the whole AVX2 encode of a 4KiB-chunk
// stripe.  This module is the reference's "plugin .so" analog done
// properly for a Python host: a C-API entry point whose per-call
// overhead is a few hundred ns, so small-op EC throughput is bounded
// by the kernel, not the binding.  Buffers come in via the buffer
// protocol (numpy arrays pass through zero-copy).

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <cstdint>
#include <cstddef>
#include <cstring>

extern "C" {
void ceph_tpu_gf_encode_best(const uint8_t*, size_t, size_t,
                             const uint8_t*, uint8_t*, size_t);
void ceph_tpu_gf_encode_batch(const uint8_t*, size_t, size_t,
                              const uint8_t*, uint8_t*, size_t, size_t);
void ceph_tpu_bitmatrix_encode(const uint8_t*, size_t, size_t,
                               const uint8_t*, uint8_t*, size_t, size_t,
                               size_t);
uint32_t ceph_tpu_crc32c(uint32_t, const uint8_t*, size_t);
}

namespace {

struct Buf {
  Py_buffer view{};
  bool ok = false;
  Buf(PyObject* obj, int flags) {
    ok = PyObject_GetBuffer(obj, &view, flags) == 0;
  }
  ~Buf() {
    if (ok) PyBuffer_Release(&view);
  }
  const uint8_t* data() const {
    return static_cast<const uint8_t*>(view.buf);
  }
  uint8_t* wdata() const { return static_cast<uint8_t*>(view.buf); }
  size_t len() const { return static_cast<size_t>(view.len); }
};

// gf_encode(matrix, rows, k, data, parity, length)
PyObject* py_gf_encode(PyObject*, PyObject* const* args,
                       Py_ssize_t nargs) {
  if (nargs != 6) {
    PyErr_SetString(PyExc_TypeError, "gf_encode takes 6 args");
    return nullptr;
  }
  const size_t rows = PyLong_AsSize_t(args[1]);
  const size_t k = PyLong_AsSize_t(args[2]);
  const size_t len = PyLong_AsSize_t(args[5]);
  if (PyErr_Occurred()) return nullptr;
  Buf matrix(args[0], PyBUF_C_CONTIGUOUS);
  Buf data(args[3], PyBUF_C_CONTIGUOUS);
  Buf parity(args[4], PyBUF_WRITABLE | PyBUF_C_CONTIGUOUS);
  if (!matrix.ok || !data.ok || !parity.ok) return nullptr;
  if (matrix.len() < rows * k || data.len() < k * len ||
      parity.len() < rows * len) {
    PyErr_SetString(PyExc_ValueError, "gf_encode: buffer too small");
    return nullptr;
  }
  Py_BEGIN_ALLOW_THREADS
  ceph_tpu_gf_encode_best(matrix.data(), rows, k, data.data(),
                          parity.wdata(), len);
  Py_END_ALLOW_THREADS
  Py_RETURN_NONE;
}

// gf_encode_batch(matrix, rows, k, data, parity, length, nstripes)
PyObject* py_gf_encode_batch(PyObject*, PyObject* const* args,
                             Py_ssize_t nargs) {
  if (nargs != 7) {
    PyErr_SetString(PyExc_TypeError, "gf_encode_batch takes 7 args");
    return nullptr;
  }
  const size_t rows = PyLong_AsSize_t(args[1]);
  const size_t k = PyLong_AsSize_t(args[2]);
  const size_t len = PyLong_AsSize_t(args[5]);
  const size_t nstripes = PyLong_AsSize_t(args[6]);
  if (PyErr_Occurred()) return nullptr;
  Buf matrix(args[0], PyBUF_C_CONTIGUOUS);
  Buf data(args[3], PyBUF_C_CONTIGUOUS);
  Buf parity(args[4], PyBUF_WRITABLE | PyBUF_C_CONTIGUOUS);
  if (!matrix.ok || !data.ok || !parity.ok) return nullptr;
  if (matrix.len() < rows * k || data.len() < nstripes * k * len ||
      parity.len() < nstripes * rows * len) {
    PyErr_SetString(PyExc_ValueError,
                    "gf_encode_batch: buffer too small");
    return nullptr;
  }
  Py_BEGIN_ALLOW_THREADS
  ceph_tpu_gf_encode_batch(matrix.data(), rows, k, data.data(),
                           parity.wdata(), len, nstripes);
  Py_END_ALLOW_THREADS
  Py_RETURN_NONE;
}

// bitmatrix_encode(bits, mw, kw, data, parity, L, w, packetsize)
PyObject* py_bitmatrix_encode(PyObject*, PyObject* const* args,
                              Py_ssize_t nargs) {
  if (nargs != 8) {
    PyErr_SetString(PyExc_TypeError, "bitmatrix_encode takes 8 args");
    return nullptr;
  }
  const size_t mw = PyLong_AsSize_t(args[1]);
  const size_t kw = PyLong_AsSize_t(args[2]);
  const size_t L = PyLong_AsSize_t(args[5]);
  const size_t w = PyLong_AsSize_t(args[6]);
  const size_t ps = PyLong_AsSize_t(args[7]);
  if (PyErr_Occurred()) return nullptr;
  Buf bits(args[0], PyBUF_C_CONTIGUOUS);
  Buf data(args[3], PyBUF_C_CONTIGUOUS);
  Buf parity(args[4], PyBUF_WRITABLE | PyBUF_C_CONTIGUOUS);
  if (!bits.ok || !data.ok || !parity.ok) return nullptr;
  if (w == 0 || ps == 0 || L % (w * ps) != 0 || kw % w != 0 ||
      mw % w != 0) {
    PyErr_SetString(PyExc_ValueError, "bitmatrix_encode: bad geometry");
    return nullptr;
  }
  if (bits.len() < mw * kw || data.len() < (kw / w) * L ||
      parity.len() < (mw / w) * L) {
    PyErr_SetString(PyExc_ValueError,
                    "bitmatrix_encode: buffer too small");
    return nullptr;
  }
  Py_BEGIN_ALLOW_THREADS
  ceph_tpu_bitmatrix_encode(bits.data(), mw, kw, data.data(),
                            parity.wdata(), L, w, ps);
  Py_END_ALLOW_THREADS
  Py_RETURN_NONE;
}

// crc32c(seed, buf) -> int
PyObject* py_crc32c(PyObject*, PyObject* const* args,
                    Py_ssize_t nargs) {
  if (nargs != 2) {
    PyErr_SetString(PyExc_TypeError, "crc32c takes 2 args");
    return nullptr;
  }
  const uint32_t seed =
      static_cast<uint32_t>(PyLong_AsUnsignedLongMask(args[0]));
  Buf buf(args[1], PyBUF_C_CONTIGUOUS);
  if (!buf.ok) return nullptr;
  uint32_t out;
  Py_BEGIN_ALLOW_THREADS
  out = ceph_tpu_crc32c(seed, buf.data(), buf.len());
  Py_END_ALLOW_THREADS
  return PyLong_FromUnsignedLong(out);
}


// ---------------------------------------------------------------------
// The denc codec (utils/denc.py) and the messenger's segment lift
// (msg/message.py), as one compiled walk of a field tree.
//
// Same bytes as the Python codec, same refusals.  The walk serves the
// EXACT types None, bool, int, float, bytes, bytearray, memoryview,
// str, list, tuple, dict, set and frozenset itself; a value of any
// other type (a @denc_type instance, a NamedTuple, a numpy scalar or
// array, a dict subclass, an int beyond a machine word) goes, for
// that value alone, to the Python helpers utils/denc.py hands over in
// `_native_hooks()`, which hold the registry and version logic.

enum : uint8_t {
  T_NONE = 0x00, T_TRUE, T_FALSE, T_INT, T_FLOAT, T_BYTES, T_STR,
  T_LIST, T_TUPLE, T_DICT, T_SET, T_NDARRAY, T_OBJ
};
constexpr int kMaxDepth = 100;        // _decode's "nesting too deep"
constexpr unsigned kMaxVarintShift = 600;

struct Hooks {
  PyObject* error;      // DencError
  PyObject* registry;   // type name -> class
  PyObject* head_tail;  // value -> (bytes, value that follows | nothing)
  PyObject* nothing;
  PyObject* construct;  // (name, class, version, fields) -> object
  PyObject* ndarray;    // (bytes, pos) -> (array, pos)
  PyObject* bigint;     // a varint's bytes -> int
};
Hooks g_hooks{};
bool g_hooked = false;
// Whole passes served here, and values handed back to Python inside
// them (utils/denc.py `counters`); written with the GIL held.
unsigned long long g_native_calls = 0;
unsigned long long g_value_callbacks = 0;

bool load_hooks() {
  if (g_hooked) return true;
  PyObject* mod = PyImport_ImportModule("ceph_tpu.utils.denc");
  if (!mod) return false;
  PyObject* t = PyObject_CallMethod(mod, "_native_hooks", nullptr);
  Py_DECREF(mod);
  if (!t) return false;
  if (!PyTuple_CheckExact(t) || PyTuple_GET_SIZE(t) != 7) {
    Py_DECREF(t);
    PyErr_SetString(PyExc_TypeError, "denc._native_hooks: want 7 hooks");
    return false;
  }
  PyObject** slots[] = {&g_hooks.error,     &g_hooks.registry,
                        &g_hooks.head_tail, &g_hooks.nothing,
                        &g_hooks.construct, &g_hooks.ndarray,
                        &g_hooks.bigint};
  for (int i = 0; i < 7; i++) {
    *slots[i] = PyTuple_GET_ITEM(t, i);
    Py_INCREF(*slots[i]);             // kept for the process's life
  }
  Py_DECREF(t);
  g_hooked = true;
  return true;
}

PyObject* denc_error(const char* what) {
  PyErr_SetString(g_hooks.error, what);
  return nullptr;
}

// -- encode -------------------------------------------------------------

struct Out {
  char* p;
  size_t len = 0, cap;
  char small[1024];
  Out() : p(small), cap(sizeof small) {}
  ~Out() {
    if (p != small) PyMem_RawFree(p);
  }
  bool reserve(size_t n) {
    if (n <= cap - len) return true;
    size_t want = cap * 2;
    while (want - len < n) want *= 2;
    char* q = static_cast<char*>(PyMem_RawMalloc(want));
    if (!q) {
      PyErr_NoMemory();
      return false;
    }
    memcpy(q, p, len);
    if (p != small) PyMem_RawFree(p);
    p = q;
    cap = want;
    return true;
  }
  bool put(uint8_t b) {
    if (!reserve(1)) return false;
    p[len++] = static_cast<char>(b);
    return true;
  }
  bool put(const void* src, size_t n) {
    if (!reserve(n)) return false;
    memcpy(p + len, src, n);
    len += n;
    return true;
  }
  bool uvarint(uint64_t n) {
    if (!reserve(10)) return false;
    while (n >= 0x80) {
      p[len++] = static_cast<char>((n & 0x7F) | 0x80);
      n >>= 7;
    }
    p[len++] = static_cast<char>(n);
    return true;
  }
  bool tagged(uint8_t tag, const void* src, size_t n) {
    return put(tag) && uvarint(n) && put(src, n);
  }
};

// What msg/message.py's `encode_iov` adds to the walk: bytes-like
// leaves of `threshold` or more move to `segs` (while it has room)
// and leave a _SegRef's encoding behind.
struct Lift {
  PyObject* segs;         // list, grows
  Py_ssize_t threshold, seg_max, audit_floor;
  const char* ref_prefix; // dumps(_SegRef(i)) up to i's varint
  Py_ssize_t ref_prefix_len;
  PyObject* bufferlist;   // the rope type
  PyObject* other;        // (value, segs) -> (bytes, value | nothing)
  PyObject* audit;        // bytes of an inline leaf -> None
};

// A bytes-like leaf of `n` (its len()) rides as a segment.
bool rides_out_of_band(const Lift* lift, Py_ssize_t n) {
  return n >= lift->threshold &&
         PyList_GET_SIZE(lift->segs) < lift->seg_max;
}

bool enc(Out& o, PyObject* obj, const Lift* lift);

// (head bytes, value that follows or `nothing`) from a Python helper.
bool enc_answer(Out& o, PyObject* res) {
  if (!res) return false;
  bool ok = false;
  if (!PyTuple_CheckExact(res) || PyTuple_GET_SIZE(res) != 2 ||
      !PyBytes_CheckExact(PyTuple_GET_ITEM(res, 0))) {
    PyErr_SetString(PyExc_TypeError,
                    "denc helper: want (bytes, value)");
  } else {
    PyObject* head = PyTuple_GET_ITEM(res, 0);
    PyObject* tail = PyTuple_GET_ITEM(res, 1);
    ok = o.put(PyBytes_AS_STRING(head), PyBytes_GET_SIZE(head)) &&
         (tail == g_hooks.nothing || enc(o, tail, nullptr));
  }
  Py_DECREF(res);
  return ok;
}

bool enc_segref(Out& o, const Lift* lift, PyObject* holder) {
  const Py_ssize_t idx = PyList_GET_SIZE(lift->segs);
  if (PyList_Append(lift->segs, holder) < 0) return false;
  return o.put(lift->ref_prefix, lift->ref_prefix_len) &&
         o.uvarint(static_cast<uint64_t>(idx) << 1);
}

bool enc_bytes_like(Out& o, PyObject* obj, const Lift* lift) {
  if (lift) {
    // len(): a memoryview's is its first dimension, as the Python has it
    const Py_ssize_t n = PyObject_Size(obj);
    if (n < 0) return false;
    if (rides_out_of_band(lift, n)) return enc_segref(o, lift, obj);
    if (n >= lift->audit_floor) {
      PyObject* r = PyObject_CallFunction(lift->audit, "n", n);
      if (!r) return false;
      Py_DECREF(r);
    }
  }
  if (PyBytes_CheckExact(obj))
    return o.tagged(T_BYTES, PyBytes_AS_STRING(obj),
                    PyBytes_GET_SIZE(obj));
  if (PyByteArray_CheckExact(obj))
    return o.tagged(T_BYTES, PyByteArray_AS_STRING(obj),
                    PyByteArray_GET_SIZE(obj));
  Py_buffer view;
  if (PyObject_GetBuffer(obj, &view, PyBUF_SIMPLE) == 0) {
    const bool ok = o.tagged(T_BYTES, view.buf, view.len);
    PyBuffer_Release(&view);
    return ok;
  }
  PyErr_Clear();                      // not contiguous: bytes(obj)
  PyObject* b = PyBytes_FromObject(obj);
  if (!b) return false;
  const bool ok =
      o.tagged(T_BYTES, PyBytes_AS_STRING(b), PyBytes_GET_SIZE(b));
  Py_DECREF(b);
  return ok;
}

bool enc_int(Out& o, PyObject* obj) {
  int overflow = 0;
  const long long v = PyLong_AsLongLongAndOverflow(obj, &overflow);
  if (overflow) {                     // beyond a machine word
    g_value_callbacks++;
    return enc_answer(o, PyObject_CallOneArg(g_hooks.head_tail, obj));
  }
  if (v == -1 && PyErr_Occurred()) return false;
  // zigzag: non-negatives even, negatives odd
  const uint64_t u = v >= 0
      ? static_cast<uint64_t>(v) << 1
      : (static_cast<uint64_t>(-(v + 1)) << 1) + 1;
  return o.put(T_INT) && o.uvarint(u);
}

bool changed_size(const char* what) {
  PyErr_Format(PyExc_RuntimeError, "%s changed size during encode", what);
  return false;
}

bool enc_sequence(Out& o, PyObject* obj, uint8_t tag, const Lift* lift) {
  const bool is_list = tag == T_LIST;
  const Py_ssize_t n =
      is_list ? PyList_GET_SIZE(obj) : PyTuple_GET_SIZE(obj);
  if (!o.put(tag) || !o.uvarint(n)) return false;
  for (Py_ssize_t i = 0; i < n; i++) {
    if (is_list && i >= PyList_GET_SIZE(obj))
      return changed_size("list");
    PyObject* v =
        is_list ? PyList_GET_ITEM(obj, i) : PyTuple_GET_ITEM(obj, i);
    Py_INCREF(v);
    const bool ok = enc(o, v, lift);
    Py_DECREF(v);
    if (!ok) return false;
  }
  return true;
}

// A key the message's own pass leaves out: `seq` and the local
// `_`-prefixed annotations (encode_iov).
bool local_key(PyObject* k) {
  if (!PyUnicode_Check(k)) return false;
  if (PyUnicode_GET_LENGTH(k) > 0 && PyUnicode_READ_CHAR(k, 0) == '_')
    return true;
  return PyUnicode_CompareWithASCIIString(k, "seq") == 0;
}

bool enc_dict(Out& o, PyObject* obj, const Lift* lift, bool top) {
  const Py_ssize_t size = PyDict_GET_SIZE(obj);
  Py_ssize_t pos = 0, want = size, done = 0;
  PyObject *k, *v;
  if (top) {
    want = 0;
    while (PyDict_Next(obj, &pos, &k, &v)) want += !local_key(k);
    pos = 0;
  }
  if (!o.put(T_DICT) || !o.uvarint(want)) return false;
  while (PyDict_Next(obj, &pos, &k, &v)) {
    if (top && local_key(k)) continue;
    Py_INCREF(k);
    Py_INCREF(v);
    // keys are not lifted: _extract_segments walks values alone
    const bool ok = enc(o, k, nullptr) && enc(o, v, lift);
    Py_DECREF(k);
    Py_DECREF(v);
    if (!ok) return false;
    done++;
    if (PyDict_GET_SIZE(obj) != size) return changed_size("dictionary");
  }
  return done == want || changed_size("dictionary");
}

bool enc_set(Out& o, PyObject* obj) {
  const Py_ssize_t n = PySet_GET_SIZE(obj);
  if (!o.put(T_SET) || !o.uvarint(n)) return false;
  PyObject* it = PyObject_GetIter(obj);
  if (!it) return false;
  Py_ssize_t done = 0;
  bool ok = true;
  while (PyObject* v = PyIter_Next(it)) {
    ok = enc(o, v, nullptr);
    Py_DECREF(v);
    done++;
    if (!ok) break;
  }
  Py_DECREF(it);
  if (!ok || PyErr_Occurred()) return false;
  return done == n || changed_size("set");
}

bool enc(Out& o, PyObject* obj, const Lift* lift) {
  PyTypeObject* tp = Py_TYPE(obj);
  if (tp == &PyUnicode_Type) {
    Py_ssize_t n;
    const char* s = PyUnicode_AsUTF8AndSize(obj, &n);
    return s && o.tagged(T_STR, s, n);
  }
  if (tp == &PyLong_Type) return enc_int(o, obj);
  if (obj == Py_None) return o.put(T_NONE);
  if (obj == Py_True) return o.put(T_TRUE);
  if (obj == Py_False) return o.put(T_FALSE);
  if (tp == &PyBytes_Type || tp == &PyByteArray_Type ||
      tp == &PyMemoryView_Type)
    return enc_bytes_like(o, obj, lift);
  if (tp == &PyFloat_Type) {
    if (!o.put(T_FLOAT) || !o.reserve(8)) return false;
    if (PyFloat_Pack8(PyFloat_AS_DOUBLE(obj), o.p + o.len, 1) < 0)
      return false;
    o.len += 8;
    return true;
  }
  const bool is_list = tp == &PyList_Type;
  if (is_list || tp == &PyTuple_Type || tp == &PyDict_Type ||
      tp == &PySet_Type || tp == &PyFrozenSet_Type) {
    if (Py_EnterRecursiveCall(" in a denc encode")) return false;
    bool ok;
    if (is_list || tp == &PyTuple_Type)
      ok = enc_sequence(o, obj, is_list ? T_LIST : T_TUPLE, lift);
    else if (tp == &PyDict_Type)
      ok = enc_dict(o, obj, lift, false);
    else
      ok = enc_set(o, obj);           // members are not lifted either
    Py_LeaveRecursiveCall();
    return ok;
  }
  if (Py_EnterRecursiveCall(" in a denc encode")) return false;
  bool ok;
  if (lift && PyObject_TypeCheck(
          obj, reinterpret_cast<PyTypeObject*>(lift->bufferlist))) {
    const Py_ssize_t n = PyObject_Size(obj);
    if (n < 0) {
      ok = false;
    } else if (rides_out_of_band(lift, n)) {
      ok = enc_segref(o, lift, obj);
    } else {                          // a small rope rides inline
      PyObject* b = PyObject_CallMethod(obj, "to_bytes", nullptr);
      ok = b && enc(o, b, nullptr);
      Py_XDECREF(b);
    }
  } else {
    g_value_callbacks++;
    ok = enc_answer(
        o, lift ? PyObject_CallFunctionObjArgs(lift->other, obj,
                                               lift->segs, nullptr)
                : PyObject_CallOneArg(g_hooks.head_tail, obj));
  }
  Py_LeaveRecursiveCall();
  return ok;
}

// -- decode -------------------------------------------------------------

// What `Message.decode` adds to the walk: where a _SegRef decodes in
// a place `_substitute_segments` reaches (through lists, tuples and
// dict values from the root), segment `i` goes instead.
struct Subst {
  PyObject* segs;         // sequence
  Py_ssize_t nsegs;
  PyObject* ref_class;    // _SegRef
  uint64_t ref_version;   // its DENC_VERSION
  PyObject* walk;         // _substitute_segments(value, segs)
};

struct Rd {
  const uint8_t* p;
  size_t len, pos = 0;
  PyObject* src;          // the caller's buffer object
  size_t left() const { return len - pos; }
};

// A varint of any length the Python accepts; `*wide` when it holds
// more than 64 bits (`*out` is then all ones).
bool rd_uvarint(Rd& r, uint64_t* out, bool* wide) {
  uint64_t n = 0;
  unsigned shift = 0;
  *wide = false;
  for (;;) {
    if (r.pos >= r.len) return denc_error("truncated input");
    const uint8_t b = r.p[r.pos++];
    const uint64_t bits = b & 0x7F;
    if (shift < 64) {
      n |= bits << shift;
      if (shift > 57 && (bits >> (64 - shift))) *wide = true;
    } else if (bits) {
      *wide = true;
    }
    if (!(b & 0x80)) break;
    shift += 7;
    if (shift > kMaxVarintShift) return denc_error("varint too long");
  }
  *out = *wide ? UINT64_MAX : n;
  return true;
}

// A length, and the bytes it counts: refuses what the buffer lacks.
const uint8_t* rd_take(Rd& r, size_t* n_out) {
  uint64_t n;
  bool wide;
  if (!rd_uvarint(r, &n, &wide)) return nullptr;
  if (wide || n > r.left()) {
    denc_error("truncated input");
    return nullptr;
  }
  const uint8_t* at = r.p + r.pos;
  r.pos += n;
  *n_out = n;
  return at;
}

PyObject* dec(Rd& r, int depth, const Subst* subst);

// A list is made at its full length up to this many elements, and
// grown by appends beyond.
constexpr uint64_t kMaxPrealloc = 4096;

// An element count larger than the bytes left can only end in a
// refusal: the elements are decoded for it and nothing is kept.
PyObject* dec_doomed(Rd& r, int depth) {
  for (;;) {                          // each value takes a byte or more
    PyObject* v = dec(r, depth, nullptr);
    if (!v) return nullptr;
    Py_DECREF(v);
  }
}

PyObject* dec_list(Rd& r, int depth, const Subst* subst, uint64_t n) {
  PyObject* out = PyList_New(n <= kMaxPrealloc ? n : 0);
  if (!out) return nullptr;
  for (uint64_t i = 0; i < n; i++) {
    PyObject* v = dec(r, depth + 1, subst);
    if (!v) {
      Py_DECREF(out);
      return nullptr;
    }
    if (n <= kMaxPrealloc) {
      PyList_SET_ITEM(out, i, v);
    } else {
      const int rc = PyList_Append(out, v);
      Py_DECREF(v);
      if (rc < 0) {
        Py_DECREF(out);
        return nullptr;
      }
    }
  }
  return out;
}

PyObject* hashable_or_denc_error(const char* what) {
  if (PyErr_ExceptionMatches(PyExc_TypeError)) {
    PyObject *t, *v, *tb;
    PyErr_Fetch(&t, &v, &tb);
    PyErr_Format(g_hooks.error, "%s: %S", what, v ? v : Py_None);
    Py_XDECREF(t);
    Py_XDECREF(v);
    Py_XDECREF(tb);
  }
  return nullptr;
}

PyObject* dec_obj(Rd& r, int depth, const Subst* subst) {
  size_t nlen;
  const uint8_t* nm = rd_take(r, &nlen);
  if (!nm) return nullptr;
  uint64_t version;
  bool wide;
  if (!rd_uvarint(r, &version, &wide)) return nullptr;
  PyObject* name = PyUnicode_DecodeUTF8(
      reinterpret_cast<const char*>(nm), nlen, "replace");
  if (!name) return nullptr;
  PyObject* klass = PyDict_GetItemWithError(g_hooks.registry, name);
  if (!klass) {
    if (!PyErr_Occurred())
      PyErr_Format(g_hooks.error, "unknown denc type %R", name);
    Py_DECREF(name);
    return nullptr;
  }
  Py_INCREF(klass);
  PyObject* out = nullptr;
  PyObject* fields = dec(r, depth + 1, nullptr);
  if (fields && subst && klass == subst->ref_class && !wide &&
      version == subst->ref_version && PyDict_CheckExact(fields)) {
    // `_substitute_segments`: the index is validated, never trusted
    PyObject* i = PyDict_GetItemString(fields, "i");
    Py_ssize_t at = -1;
    if (i && PyLong_Check(i)) {
      at = PyLong_AsSsize_t(i);
      if (at == -1 && PyErr_Occurred()) PyErr_Clear();
    }
    if (at < 0 || at >= subst->nsegs)
      PyErr_Format(PyExc_ValueError,
                   "segment ref %R outside %zd segments",
                   i ? i : Py_None, subst->nsegs);
    else
      out = PySequence_GetItem(subst->segs, at);
  } else if (fields) {
    g_value_callbacks++;
    PyObject* ver = wide ? PyLong_FromString("18446744073709551616",
                                             nullptr, 10)
                         : PyLong_FromUnsignedLongLong(version);
    if (ver)
      out = PyObject_CallFunctionObjArgs(g_hooks.construct, name, klass,
                                         ver, fields, nullptr);
    Py_XDECREF(ver);
    if (out && subst &&
        (PyObject_TypeCheck(
             out, reinterpret_cast<PyTypeObject*>(subst->ref_class)) ||
         PyTuple_Check(out) || PyList_Check(out) || PyDict_Check(out))) {
      // a _SegRef of another version, or a struct that is itself a
      // tuple, list or dict (a NamedTuple), which that walk enters:
      // its own code, for this value alone
      g_value_callbacks++;
      PyObject* walked = PyObject_CallFunctionObjArgs(
          subst->walk, out, subst->segs, nullptr);
      Py_DECREF(out);
      out = walked;
    }
  }
  Py_XDECREF(fields);
  Py_DECREF(klass);
  Py_DECREF(name);
  return out;
}

PyObject* dec(Rd& r, int depth, const Subst* subst) {
  if (depth > kMaxDepth) return denc_error("nesting too deep");
  if (r.pos >= r.len) return denc_error("truncated input");
  const uint8_t tag = r.p[r.pos++];
  uint64_t n;
  bool wide;
  switch (tag) {
    case T_NONE:
      Py_RETURN_NONE;
    case T_TRUE:
      Py_RETURN_TRUE;
    case T_FALSE:
      Py_RETURN_FALSE;
    case T_INT: {
      const size_t start = r.pos;
      if (!rd_uvarint(r, &n, &wide)) return nullptr;
      if (!wide)
        return PyLong_FromLongLong(static_cast<long long>(n >> 1) ^
                                   -static_cast<long long>(n & 1));
      g_value_callbacks++;
      PyObject* raw = PyBytes_FromStringAndSize(
          reinterpret_cast<const char*>(r.p + start), r.pos - start);
      if (!raw) return nullptr;
      PyObject* out = PyObject_CallOneArg(g_hooks.bigint, raw);
      Py_DECREF(raw);
      return out;
    }
    case T_FLOAT: {
      if (r.left() < 8) return denc_error("truncated input");
      const double d = PyFloat_Unpack8(
          reinterpret_cast<const char*>(r.p + r.pos), 1);
      if (d == -1.0 && PyErr_Occurred()) return nullptr;
      r.pos += 8;
      return PyFloat_FromDouble(d);
    }
    case T_BYTES: {
      size_t len;
      const uint8_t* at = rd_take(r, &len);
      if (!at) return nullptr;
      return PyBytes_FromStringAndSize(
          reinterpret_cast<const char*>(at), len);
    }
    case T_STR: {
      size_t len;
      const uint8_t* at = rd_take(r, &len);
      if (!at) return nullptr;
      PyObject* s = PyUnicode_DecodeUTF8(
          reinterpret_cast<const char*>(at), len, nullptr);
      if (!s && PyErr_ExceptionMatches(PyExc_UnicodeDecodeError)) {
        PyObject *t, *v, *tb;
        PyErr_Fetch(&t, &v, &tb);
        PyErr_NormalizeException(&t, &v, &tb);
        PyErr_Format(g_hooks.error, "bad utf-8: %S", v ? v : Py_None);
        Py_XDECREF(t);
        Py_XDECREF(v);
        Py_XDECREF(tb);
      }
      return s;
    }
    case T_LIST:
    case T_TUPLE: {
      if (!rd_uvarint(r, &n, &wide)) return nullptr;
      if (wide || n > r.left()) return dec_doomed(r, depth + 1);
      PyObject* out = dec_list(r, depth, subst, n);
      if (out && tag == T_TUPLE) {
        PyObject* t = PyList_AsTuple(out);
        Py_DECREF(out);
        return t;
      }
      return out;
    }
    case T_DICT: {
      if (!rd_uvarint(r, &n, &wide)) return nullptr;
      PyObject* out = PyDict_New();
      if (!out) return nullptr;
      for (uint64_t i = 0; wide || i < n; i++) {
        PyObject* k = dec(r, depth + 1, nullptr);
        PyObject* v = k ? dec(r, depth + 1, subst) : nullptr;
        if (v && PyDict_SetItem(out, k, v) < 0) {
          hashable_or_denc_error("unhashable dict key");
          Py_CLEAR(v);
        }
        Py_XDECREF(k);
        if (!v) {
          Py_DECREF(out);
          return nullptr;
        }
        Py_DECREF(v);
      }
      return out;
    }
    case T_SET: {
      if (!rd_uvarint(r, &n, &wide)) return nullptr;
      PyObject* out = PySet_New(nullptr);
      if (!out) return nullptr;
      for (uint64_t i = 0; wide || i < n; i++) {
        PyObject* v = dec(r, depth + 1, nullptr);
        if (v && PySet_Add(out, v) < 0) {
          hashable_or_denc_error("unhashable set member");
          Py_CLEAR(v);
        }
        if (!v) {
          Py_DECREF(out);
          return nullptr;
        }
        Py_DECREF(v);
      }
      return out;
    }
    case T_NDARRAY: {
      g_value_callbacks++;
      // the helper reads from a bytes object: the caller's own where
      // it is one, else a copy of what is left
      PyObject* src;
      size_t base = 0;
      if (PyBytes_CheckExact(r.src)) {
        src = r.src;
        Py_INCREF(src);
      } else {
        base = r.pos;
        src = PyBytes_FromStringAndSize(
            reinterpret_cast<const char*>(r.p + r.pos), r.left());
        if (!src) return nullptr;
      }
      PyObject* res = PyObject_CallFunction(g_hooks.ndarray, "On", src,
                                            (Py_ssize_t)(r.pos - base));
      Py_DECREF(src);
      if (!res) return nullptr;
      PyObject* out = nullptr;
      Py_ssize_t pos = -1;
      if (PyTuple_CheckExact(res) && PyTuple_GET_SIZE(res) == 2)
        pos = PyLong_AsSsize_t(PyTuple_GET_ITEM(res, 1));
      if (pos < 0 || base + pos < r.pos || base + pos > r.len) {
        if (!PyErr_Occurred())
          PyErr_SetString(PyExc_TypeError,
                          "denc helper: want (array, pos)");
      } else {
        r.pos = base + pos;
        out = PyTuple_GET_ITEM(res, 0);
        Py_INCREF(out);
      }
      Py_DECREF(res);
      return out;
    }
    case T_OBJ:
      return dec_obj(r, depth, subst);
    default:
      PyErr_Format(g_hooks.error, "bad tag 0x%02x", tag);
      return nullptr;
  }
}

// The whole of a buffer as one value: trailing bytes are refused.
PyObject* dec_all(PyObject* buf, const Subst* subst) {
  Py_buffer view;
  PyObject* copy = nullptr;           // bytes(buf), where it has to be
  if (PyObject_GetBuffer(buf, &view, PyBUF_SIMPLE) < 0) {
    PyErr_Clear();
    copy = PyBytes_FromObject(buf);
    if (!copy || PyObject_GetBuffer(copy, &view, PyBUF_SIMPLE) < 0) {
      Py_XDECREF(copy);
      return nullptr;
    }
  }
  Rd r{static_cast<const uint8_t*>(view.buf),
       static_cast<size_t>(view.len), 0, copy ? copy : buf};
  PyObject* out = dec(r, 0, subst);
  if (out && r.pos != r.len) {
    Py_DECREF(out);
    out = nullptr;
    PyErr_Format(g_hooks.error, "%zu trailing bytes", r.len - r.pos);
  }
  PyBuffer_Release(&view);
  Py_XDECREF(copy);
  return out;
}

// denc_dumps(obj) -> bytes
PyObject* py_denc_dumps(PyObject*, PyObject* obj) {
  if (!load_hooks()) return nullptr;
  g_native_calls++;
  Out o;
  if (!enc(o, obj, nullptr)) return nullptr;
  return PyBytes_FromStringAndSize(o.p, o.len);
}

// denc_loads(buf) -> obj
PyObject* py_denc_loads(PyObject*, PyObject* buf) {
  if (!load_hooks()) return nullptr;
  g_native_calls++;
  return dec_all(buf, nullptr);
}

// The message's side of the codec: msg/message.py `_NATIVE_CTX`.
enum {
  CTX_THRESHOLD, CTX_SEG_MAX, CTX_AUDIT_FLOOR, CTX_REF_PREFIX,
  CTX_REF_CLASS, CTX_REF_VERSION, CTX_BUFFERLIST, CTX_LIFT_OTHER,
  CTX_AUDIT, CTX_SUBSTITUTE, CTX_SIZE
};

bool check_ctx(PyObject* ctx) {
  if (PyTuple_CheckExact(ctx) && PyTuple_GET_SIZE(ctx) == CTX_SIZE &&
      PyBytes_CheckExact(PyTuple_GET_ITEM(ctx, CTX_REF_PREFIX)) &&
      PyType_Check(PyTuple_GET_ITEM(ctx, CTX_REF_CLASS)) &&
      PyType_Check(PyTuple_GET_ITEM(ctx, CTX_BUFFERLIST)))
    return true;
  PyErr_SetString(PyExc_TypeError, "denc: bad message context");
  return false;
}

// denc_dumps_msg(fields, segs, ctx) -> payload; segs grows
PyObject* py_denc_dumps_msg(PyObject*, PyObject* const* args,
                            Py_ssize_t nargs) {
  if (nargs != 3 || !PyDict_CheckExact(args[0]) ||
      !PyList_CheckExact(args[1])) {
    PyErr_SetString(PyExc_TypeError,
                    "denc_dumps_msg(dict, list, ctx)");
    return nullptr;
  }
  PyObject* ctx = args[2];
  if (!check_ctx(ctx) || !load_hooks()) return nullptr;
  PyObject* prefix = PyTuple_GET_ITEM(ctx, CTX_REF_PREFIX);
  Lift lift{args[1],
            PyLong_AsSsize_t(PyTuple_GET_ITEM(ctx, CTX_THRESHOLD)),
            PyLong_AsSsize_t(PyTuple_GET_ITEM(ctx, CTX_SEG_MAX)),
            PyLong_AsSsize_t(PyTuple_GET_ITEM(ctx, CTX_AUDIT_FLOOR)),
            PyBytes_AS_STRING(prefix),
            PyBytes_GET_SIZE(prefix),
            PyTuple_GET_ITEM(ctx, CTX_BUFFERLIST),
            PyTuple_GET_ITEM(ctx, CTX_LIFT_OTHER),
            PyTuple_GET_ITEM(ctx, CTX_AUDIT)};
  if (PyErr_Occurred()) return nullptr;
  g_native_calls++;
  Out o;
  if (Py_EnterRecursiveCall(" in a denc encode")) return nullptr;
  const bool ok = enc_dict(o, args[0], &lift, true);
  Py_LeaveRecursiveCall();
  if (!ok) return nullptr;
  return PyBytes_FromStringAndSize(o.p, o.len);
}

// denc_loads_msg(payload, segs, ctx) -> field dict
PyObject* py_denc_loads_msg(PyObject*, PyObject* const* args,
                            Py_ssize_t nargs) {
  if (nargs != 3) {
    PyErr_SetString(PyExc_TypeError,
                    "denc_loads_msg(payload, segs, ctx)");
    return nullptr;
  }
  PyObject* ctx = args[2];
  if (!check_ctx(ctx) || !load_hooks()) return nullptr;
  Subst subst{args[1], PySequence_Size(args[1]),
              PyTuple_GET_ITEM(ctx, CTX_REF_CLASS),
              PyLong_AsUnsignedLongLong(
                  PyTuple_GET_ITEM(ctx, CTX_REF_VERSION)),
              PyTuple_GET_ITEM(ctx, CTX_SUBSTITUTE)};
  if (PyErr_Occurred()) return nullptr;
  g_native_calls++;
  PyObject* fields = dec_all(args[0], &subst);
  if (fields && !PyDict_Check(fields)) {
    Py_DECREF(fields);
    return denc_error("message payload must be a field dict");
  }
  return fields;
}

// denc_counters() -> (native_calls, value_callbacks)
PyObject* py_denc_counters(PyObject*, PyObject*) {
  return Py_BuildValue("KK", g_native_calls, g_value_callbacks);
}

PyMethodDef kMethods[] = {
    {"gf_encode", reinterpret_cast<PyCFunction>(py_gf_encode),
     METH_FASTCALL, "parity = matrix x data over GF(2^8)"},
    {"gf_encode_batch",
     reinterpret_cast<PyCFunction>(py_gf_encode_batch), METH_FASTCALL,
     "batched stripes: parity[S] = matrix x data[S]"},
    {"bitmatrix_encode",
     reinterpret_cast<PyCFunction>(py_bitmatrix_encode), METH_FASTCALL,
     "packetized GF(2) bitmatrix encode"},
    {"crc32c", reinterpret_cast<PyCFunction>(py_crc32c), METH_FASTCALL,
     "CRC32C (Castagnoli)"},
    {"denc_dumps", py_denc_dumps, METH_O, "denc.dumps"},
    {"denc_loads", py_denc_loads, METH_O, "denc.loads"},
    {"denc_dumps_msg",
     reinterpret_cast<PyCFunction>(py_denc_dumps_msg), METH_FASTCALL,
     "a message's payload, its large leaves lifted into segments"},
    {"denc_loads_msg",
     reinterpret_cast<PyCFunction>(py_denc_loads_msg), METH_FASTCALL,
     "a message's field dict, segments put where their refs decode"},
    {"denc_counters", py_denc_counters, METH_NOARGS,
     "(native_calls, value_callbacks)"},
    {nullptr, nullptr, 0, nullptr}};

PyModuleDef kModule = {PyModuleDef_HEAD_INIT, "_ceph_tpu_native",
                       "native EC kernel bindings", -1, kMethods};

}  // namespace

PyMODINIT_FUNC PyInit__ceph_tpu_native(void) {
  return PyModule_Create(&kModule);
}
