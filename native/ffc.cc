// C API implementation — embeds CPython and drives flexflow_tpu.
//
// Reference analog: python/flexflow_c.cc (1,937 LoC of flat wrappers over
// FFModel). Architecture differs by necessity: the reference's runtime is
// C++ underneath a C API underneath Python; ours is Python/JAX underneath
// a C API, so handles hold PyObject* and every entry point runs a small
// amount of Python. Single-threaded embedding contract (one OS thread owns
// the interpreter), matching how the reference's cffi layer is used.

#include "flexflow_tpu_c.h"

#include <Python.h>

#include <cstdio>
#include <cstring>
#include <string>
#include <vector>

namespace {

std::string g_error;

void set_error_from_python() {
  PyObject *type = nullptr, *value = nullptr, *tb = nullptr;
  PyErr_Fetch(&type, &value, &tb);
  if (value != nullptr) {
    PyObject *s = PyObject_Str(value);
    g_error = s ? PyUnicode_AsUTF8(s) : "unknown python error";
    Py_XDECREF(s);
  } else {
    g_error = "unknown error";
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(tb);
}

// module caches are globals (not function-local statics) so ffc_finalize
// can reset them — otherwise a finalize/init cycle would dereference
// pointers from the destroyed interpreter
PyObject *g_ff_module = nullptr;
PyObject *g_np_module = nullptr;

PyObject *ff_module() {
  if (g_ff_module == nullptr) {
    g_ff_module = PyImport_ImportModule("flexflow_tpu");
    if (g_ff_module == nullptr) set_error_from_python();
  }
  return g_ff_module;
}

PyObject *np_module() {
  if (g_np_module == nullptr) {
    g_np_module = PyImport_ImportModule("numpy");
    if (g_np_module == nullptr) set_error_from_python();
  }
  return g_np_module;
}

// call obj.method(*args) returning new ref (nullptr + error set on failure)
PyObject *call_method(PyObject *obj, const char *name, PyObject *args,
                      PyObject *kwargs = nullptr) {
  PyObject *fn = PyObject_GetAttrString(obj, name);
  if (fn == nullptr) {
    set_error_from_python();
    return nullptr;
  }
  PyObject *out = PyObject_Call(fn, args, kwargs);
  Py_DECREF(fn);
  if (out == nullptr) set_error_from_python();
  return out;
}

const char *dt_name(ffc_dtype_t d) {
  switch (d) {
    case FFC_DT_INT32: return "INT32";
    case FFC_DT_BFLOAT16: return "BFLOAT16";
    default: return "FLOAT";
  }
}

const char *act_name(ffc_activation_t a) {
  switch (a) {
    case FFC_AC_RELU: return "RELU";
    case FFC_AC_SIGMOID: return "SIGMOID";
    case FFC_AC_TANH: return "TANH";
    case FFC_AC_GELU: return "GELU";
    default: return "NONE";
  }
}

PyObject *enum_member(const char *enum_name, const char *member) {
  PyObject *mod = ff_module();
  if (!mod) return nullptr;
  PyObject *en = PyObject_GetAttrString(mod, enum_name);
  if (!en) { set_error_from_python(); return nullptr; }
  PyObject *m = PyObject_GetAttrString(en, member);
  Py_DECREF(en);
  if (!m) set_error_from_python();
  return m;
}

// numpy array from a host buffer (copies; caller keeps ownership).
// force_2d keeps the (rows, row_elems) shape even when row_elems == 1 —
// token/prompt buffers must stay 2-D for fit/generate.
PyObject *np_from_buffer(const void *data, int64_t n_elems,
                         const char *dtype, int64_t rows, int64_t row_elems,
                         bool force_2d = false) {
  PyObject *np = np_module();
  if (!np) return nullptr;
  PyObject *mem = PyMemoryView_FromMemory(
      reinterpret_cast<char *>(const_cast<void *>(data)),
      n_elems * (strcmp(dtype, "int32") == 0 ? 4 : 4), PyBUF_READ);
  if (!mem) { set_error_from_python(); return nullptr; }
  PyObject *arr = PyObject_CallMethod(np, "frombuffer", "Os", mem, dtype);
  Py_DECREF(mem);
  if (!arr) { set_error_from_python(); return nullptr; }
  PyObject *shaped;
  if (row_elems > 1 || force_2d) {
    shaped = PyObject_CallMethod(arr, "reshape", "(LL)", (long long)rows,
                                 (long long)row_elems);
  } else {
    shaped = PyObject_CallMethod(arr, "reshape", "(L)", (long long)rows);
  }
  Py_DECREF(arr);
  if (!shaped) { set_error_from_python(); return nullptr; }
  // copy so the framework may keep the array beyond the caller's buffer
  PyObject *copied = PyObject_CallMethod(shaped, "copy", nullptr);
  Py_DECREF(shaped);
  if (!copied) set_error_from_python();
  return copied;
}

struct ModelState {
  PyObject *model;        // FFModel
  PyObject *last_metrics; // PerfMetrics from the last fit
  std::vector<long long> input_dims;  // first input's dims (for fit reshape)
};

}  // namespace

extern "C" {

const char *ffc_last_error(void) { return g_error.c_str(); }

int ffc_init(int argc, char **argv) {
  if (Py_IsInitialized()) return 0;
  Py_Initialize();
  // FFC_PLATFORM / FFC_CPU_DEVICES pin the jax backend BEFORE any backend
  // touch
  PyRun_SimpleString(
      "import os\n"
      "_p = os.environ.get('FFC_PLATFORM')\n"
      "if _p:\n"
      "    import jax\n"
      "    jax.config.update('jax_platforms', _p)\n"
      "    _n = os.environ.get('FFC_CPU_DEVICES')\n"
      "    if _n:\n"
      "        jax.config.update('jax_num_cpu_devices', int(_n))\n");
  if (!ff_module()) return -1;
  (void)argc;
  (void)argv;
  return 0;
}

void ffc_finalize(void) {
  if (Py_IsInitialized()) {
    Py_XDECREF(g_ff_module);
    Py_XDECREF(g_np_module);
    Py_Finalize();
  }
  g_ff_module = nullptr;
  g_np_module = nullptr;
}

ffc_config_t ffc_config_create(int batch_size, int num_devices) {
  g_error.clear();
  PyObject *mod = ff_module();
  if (!mod) return nullptr;
  PyObject *cls = PyObject_GetAttrString(mod, "FFConfig");
  if (!cls) { set_error_from_python(); return nullptr; }
  PyObject *kwargs = Py_BuildValue("{s:i}", "batch_size", batch_size);
  if (num_devices > 0) {
    PyObject *nd = PyLong_FromLong(num_devices);
    PyDict_SetItemString(kwargs, "num_devices", nd);
    Py_DECREF(nd);
  }
  PyObject *args = PyTuple_New(0);
  PyObject *cfg = PyObject_Call(cls, args, kwargs);
  Py_DECREF(cls);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  if (!cfg) set_error_from_python();
  return cfg;
}

void ffc_config_destroy(ffc_config_t cfg) {
  Py_XDECREF(reinterpret_cast<PyObject *>(cfg));
}

ffc_model_t ffc_model_create(ffc_config_t cfg) {
  g_error.clear();
  PyObject *mod = ff_module();
  if (!mod) return nullptr;
  PyObject *cls = PyObject_GetAttrString(mod, "FFModel");
  if (!cls) { set_error_from_python(); return nullptr; }
  PyObject *model = PyObject_CallFunctionObjArgs(
      cls, reinterpret_cast<PyObject *>(cfg), nullptr);
  Py_DECREF(cls);
  if (!model) { set_error_from_python(); return nullptr; }
  auto *st = new ModelState{model, nullptr, {}};
  return st;
}

void ffc_model_destroy(ffc_model_t handle) {
  auto *st = reinterpret_cast<ModelState *>(handle);
  if (!st) return;
  Py_XDECREF(st->model);
  Py_XDECREF(st->last_metrics);
  delete st;
}

ffc_tensor_t ffc_model_create_tensor(ffc_model_t handle, int ndims,
                                     const int64_t *dims, ffc_dtype_t dtype) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *dim_tuple = PyTuple_New(ndims);
  for (int i = 0; i < ndims; i++) {
    PyTuple_SetItem(dim_tuple, i, PyLong_FromLongLong(dims[i]));
  }
  PyObject *dt_obj = enum_member("DataType", dt_name(dtype));
  if (!dt_obj) { Py_DECREF(dim_tuple); return nullptr; }
  PyObject *args = PyTuple_Pack(2, dim_tuple, dt_obj);
  PyObject *t = call_method(st->model, "create_tensor", args);
  Py_DECREF(args);
  Py_DECREF(dim_tuple);
  Py_DECREF(dt_obj);
  if (t && st->input_dims.empty()) {
    for (int i = 0; i < ndims; i++) st->input_dims.push_back(dims[i]);
  }
  return t;
}

ffc_tensor_t ffc_model_dense(ffc_model_t handle, ffc_tensor_t input,
                             int out_dim, ffc_activation_t act, int use_bias) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *act_obj = enum_member("ActiMode", act_name(act));
  if (!act_obj) return nullptr;
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *kwargs = Py_BuildValue("{s:i,s:O,s:i}", "out_dim", out_dim,
                                   "activation", act_obj, "use_bias",
                                   use_bias ? 1 : 0);
  PyObject *t = call_method(st->model, "dense", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(act_obj);
  return t;
}

ffc_tensor_t ffc_model_conv2d(ffc_model_t handle, ffc_tensor_t input,
                              int out_channels, int kernel_h, int kernel_w,
                              int stride_h, int stride_w, int padding_h,
                              int padding_w, ffc_activation_t act) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *act_obj = enum_member("ActiMode", act_name(act));
  if (!act_obj) return nullptr;
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *kwargs = Py_BuildValue(
      "{s:i,s:i,s:i,s:i,s:i,s:i,s:i,s:O}", "out_channels", out_channels,
      "kernel_h", kernel_h, "kernel_w", kernel_w, "stride_h", stride_h,
      "stride_w", stride_w, "padding_h", padding_h, "padding_w", padding_w,
      "activation", act_obj);
  PyObject *t = call_method(st->model, "conv2d", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(act_obj);
  return t;
}

ffc_tensor_t ffc_model_pool2d(ffc_model_t handle, ffc_tensor_t input,
                              int kernel_h, int kernel_w, int stride_h,
                              int stride_w, int padding_h, int padding_w,
                              int is_max) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *pt = enum_member("PoolType", is_max ? "MAX" : "AVG");
  if (!pt) return nullptr;
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *kwargs = Py_BuildValue(
      "{s:i,s:i,s:i,s:i,s:i,s:i,s:O}", "kernel_h", kernel_h, "kernel_w",
      kernel_w, "stride_h", stride_h, "stride_w", stride_w, "padding_h",
      padding_h, "padding_w", padding_w, "pool_type", pt);
  PyObject *t = call_method(st->model, "pool2d", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(pt);
  return t;
}

ffc_tensor_t ffc_model_embedding(ffc_model_t handle, ffc_tensor_t input,
                                 int num_entries, int out_dim) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *kwargs = Py_BuildValue("{s:i,s:i}", "num_entries", num_entries,
                                   "out_dim", out_dim);
  PyObject *t = call_method(st->model, "embedding", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  return t;
}

static ffc_tensor_t unary(ffc_model_t handle, ffc_tensor_t input,
                          const char *name) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *t = call_method(st->model, name, args);
  Py_DECREF(args);
  return t;
}

ffc_tensor_t ffc_model_relu(ffc_model_t m, ffc_tensor_t x) {
  return unary(m, x, "relu");
}
ffc_tensor_t ffc_model_softmax(ffc_model_t m, ffc_tensor_t x) {
  return unary(m, x, "softmax");
}
ffc_tensor_t ffc_model_flat(ffc_model_t m, ffc_tensor_t x) {
  return unary(m, x, "flat");
}

ffc_tensor_t ffc_model_add(ffc_model_t handle, ffc_tensor_t a, ffc_tensor_t b) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(2, reinterpret_cast<PyObject *>(a),
                                reinterpret_cast<PyObject *>(b));
  PyObject *t = call_method(st->model, "add", args);
  Py_DECREF(args);
  return t;
}

ffc_tensor_t ffc_model_concat(ffc_model_t handle, int n,
                              const ffc_tensor_t *tensors, int axis) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *lst = PyList_New(n);
  for (int i = 0; i < n; i++) {
    PyObject *t = reinterpret_cast<PyObject *>(tensors[i]);
    Py_INCREF(t);
    PyList_SetItem(lst, i, t);
  }
  PyObject *args = PyTuple_Pack(1, lst);
  PyObject *kwargs = Py_BuildValue("{s:i}", "axis", axis);
  PyObject *t = call_method(st->model, "concat", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(lst);
  return t;
}

void ffc_tensor_destroy(ffc_tensor_t t) {
  Py_XDECREF(reinterpret_cast<PyObject *>(t));
}

ffc_tensor_t ffc_model_embedding_aggr(ffc_model_t handle, ffc_tensor_t input,
                                      int num_entries, int out_dim,
                                      ffc_aggr_t aggr, ffc_dtype_t dtype) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  const char *an = aggr == FFC_AGGR_SUM ? "SUM"
                   : aggr == FFC_AGGR_AVG ? "AVG" : "NONE";
  PyObject *aggr_obj = enum_member("AggrMode", an);
  PyObject *dt_obj = enum_member("DataType", dt_name(dtype));
  if (!aggr_obj || !dt_obj) {
    Py_XDECREF(aggr_obj);
    Py_XDECREF(dt_obj);
    return nullptr;
  }
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *kwargs = Py_BuildValue(
      "{s:i,s:i,s:O,s:O}", "num_entries", num_entries, "out_dim", out_dim,
      "aggr", aggr_obj, "dtype", dt_obj);
  PyObject *t = call_method(st->model, "embedding", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(aggr_obj);
  Py_DECREF(dt_obj);
  return t;
}

ffc_tensor_t ffc_model_multihead_attention(ffc_model_t handle, ffc_tensor_t q,
                                           ffc_tensor_t k, ffc_tensor_t v,
                                           int embed_dim, int num_heads,
                                           int kv_heads, int causal, int rope,
                                           float rope_theta) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(3, reinterpret_cast<PyObject *>(q),
                                reinterpret_cast<PyObject *>(k),
                                reinterpret_cast<PyObject *>(v));
  PyObject *kwargs = Py_BuildValue(
      "{s:i,s:i,s:O,s:O,s:O,s:f}", "embed_dim", embed_dim, "num_heads",
      num_heads, "causal", causal ? Py_True : Py_False, "rope",
      rope ? Py_True : Py_False, "bias", Py_False, "rope_theta", rope_theta);
  if (kv_heads > 0) {
    PyObject *kv = PyLong_FromLong(kv_heads);
    PyDict_SetItemString(kwargs, "kv_heads", kv);
    Py_DECREF(kv);
  }
  PyObject *t = call_method(st->model, "multihead_attention", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  return t;
}

ffc_tensor_t ffc_model_rms_norm(ffc_model_t handle, ffc_tensor_t input,
                                float eps) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *kwargs = Py_BuildValue("{s:f}", "eps", eps);
  PyObject *t = call_method(st->model, "rms_norm", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  return t;
}

ffc_tensor_t ffc_model_layer_norm(ffc_model_t handle, ffc_tensor_t input,
                                  float eps) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *kwargs = Py_BuildValue("{s:f}", "eps", eps);
  PyObject *t = call_method(st->model, "layer_norm", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  return t;
}

// shared compile tail: consumes a NEW reference to `opt`
static int compile_with_optimizer(ModelState *st, PyObject *opt,
                                  ffc_loss_t loss) {
  const char *ln = loss == FFC_LOSS_CCE ? "CATEGORICAL_CROSSENTROPY"
                   : loss == FFC_LOSS_MSE ? "MEAN_SQUARED_ERROR_AVG_REDUCE"
                   : "SPARSE_CATEGORICAL_CROSSENTROPY";
  PyObject *loss_obj = enum_member("LossType", ln);
  PyObject *acc = enum_member("MetricsType", "ACCURACY");
  if (!loss_obj || !acc) {
    Py_XDECREF(loss_obj);
    Py_XDECREF(acc);
    Py_DECREF(opt);
    return -1;
  }
  PyObject *metrics = PyList_New(1);
  Py_INCREF(acc);
  PyList_SetItem(metrics, 0, acc);
  PyObject *args = PyTuple_New(0);
  PyObject *kwargs = Py_BuildValue("{s:O,s:O,s:O}", "optimizer", opt,
                                   "loss_type", loss_obj, "metrics", metrics);
  PyObject *r = call_method(st->model, "compile", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(opt);
  Py_DECREF(loss_obj);
  Py_DECREF(acc);
  Py_DECREF(metrics);
  if (!r) return -1;
  Py_DECREF(r);
  return 0;
}

int ffc_model_compile(ffc_model_t handle, ffc_loss_t loss, float lr) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *mod = ff_module();
  PyObject *opt_cls = PyObject_GetAttrString(mod, "SGDOptimizer");
  if (!opt_cls) { set_error_from_python(); return -1; }
  PyObject *okw = Py_BuildValue("{s:f}", "lr", lr);
  PyObject *oargs = PyTuple_New(0);
  PyObject *opt = PyObject_Call(opt_cls, oargs, okw);
  Py_DECREF(opt_cls);
  Py_DECREF(oargs);
  Py_DECREF(okw);
  if (!opt) { set_error_from_python(); return -1; }
  return compile_with_optimizer(st, opt, loss);
}


int ffc_model_compile_adam(ffc_model_t handle, ffc_loss_t loss, float lr,
                           float beta1, float beta2, float epsilon,
                           float weight_decay) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *mod = ff_module();
  PyObject *opt_cls = PyObject_GetAttrString(mod, "AdamOptimizer");
  if (!opt_cls) { set_error_from_python(); return -1; }
  PyObject *okw = Py_BuildValue("{s:f,s:f,s:f,s:f,s:f}", "lr", lr, "beta1",
                                beta1, "beta2", beta2, "epsilon", epsilon,
                                "weight_decay", weight_decay);
  PyObject *oargs = PyTuple_New(0);
  PyObject *opt = PyObject_Call(opt_cls, oargs, okw);
  Py_DECREF(opt_cls);
  Py_DECREF(oargs);
  Py_DECREF(okw);
  if (!opt) { set_error_from_python(); return -1; }
  return compile_with_optimizer(st, opt, loss);
}

// reshape a flat (n, row_elems) buffer to the model's first input tensor
// dims (n, d1, d2, ...) when the input is >2-D; consumes `xa` on failure
static PyObject *reshape_to_input_dims(ModelState *st, PyObject *xa,
                                       int64_t n) {
  if (st->input_dims.size() <= 2) return xa;
  PyObject *shape = PyTuple_New(st->input_dims.size());
  PyTuple_SetItem(shape, 0, PyLong_FromLongLong(n));
  for (size_t i = 1; i < st->input_dims.size(); i++) {
    PyTuple_SetItem(shape, i, PyLong_FromLongLong(st->input_dims[i]));
  }
  PyObject *xr = PyObject_CallMethod(xa, "reshape", "(O)", shape);
  Py_DECREF(shape);
  Py_DECREF(xa);
  if (!xr) set_error_from_python();
  return xr;
}

int64_t ffc_model_fit(ffc_model_t handle, const float *x, const int32_t *y,
                      int64_t n, int64_t x_row_elems, int epochs) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *xa = np_from_buffer(x, n * x_row_elems, "float32", n, x_row_elems);
  if (!xa) return -1;
  // reshape x to the first input tensor's trailing dims
  xa = reshape_to_input_dims(st, xa, n);
  if (!xa) return -1;
  PyObject *ya = np_from_buffer(y, n, "int32", n, 1);
  if (!ya) { Py_DECREF(xa); return -1; }
  PyObject *args = PyTuple_Pack(2, xa, ya);
  PyObject *kwargs = Py_BuildValue("{s:i,s:O}", "epochs", epochs, "verbose",
                                   Py_False);
  PyObject *metrics = call_method(st->model, "fit", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(xa);
  Py_DECREF(ya);
  if (!metrics) return -1;
  Py_XDECREF(st->last_metrics);
  st->last_metrics = metrics;
  PyObject *ta = PyObject_GetAttrString(metrics, "train_all");
  int64_t out = ta ? PyLong_AsLongLong(ta) : -1;
  Py_XDECREF(ta);
  return out;
}

int ffc_model_predict(ffc_model_t handle, const float *x, int64_t n,
                      int64_t x_row_elems, float *out, int64_t out_elems) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *xa = np_from_buffer(x, n * x_row_elems, "float32", n, x_row_elems);
  if (!xa) return -1;
  xa = reshape_to_input_dims(st, xa, n);
  if (!xa) return -1;
  PyObject *args = PyTuple_Pack(1, xa);
  PyObject *empty = PyDict_New();
  PyObject *pred = call_method(st->model, "predict", args, empty);
  Py_DECREF(args);
  Py_DECREF(empty);
  Py_DECREF(xa);
  if (!pred) return -1;
  PyObject *np = np_module();
  PyObject *flat = PyObject_CallMethod(np, "ascontiguousarray", "O", pred);
  Py_DECREF(pred);
  if (!flat) { set_error_from_python(); return -1; }
  PyObject *f32 = PyObject_CallMethod(flat, "astype", "s", "float32");
  Py_DECREF(flat);
  if (!f32) { set_error_from_python(); return -1; }
  Py_buffer view;
  if (PyObject_GetBuffer(f32, &view, PyBUF_CONTIG_RO) != 0) {
    set_error_from_python();
    Py_DECREF(f32);
    return -1;
  }
  int64_t want = n * out_elems * (int64_t)sizeof(float);
  int64_t have = (int64_t)view.len;
  memcpy(out, view.buf, want < have ? want : have);
  PyBuffer_Release(&view);
  Py_DECREF(f32);
  return 0;
}

double ffc_model_last_accuracy(ffc_model_t handle) {
  auto *st = reinterpret_cast<ModelState *>(handle);
  if (!st || !st->last_metrics) return -1.0;
  PyObject *c = PyObject_GetAttrString(st->last_metrics, "train_correct");
  PyObject *a = PyObject_GetAttrString(st->last_metrics, "train_all");
  double res = -1.0;
  if (c && a && PyLong_AsLongLong(a) > 0) {
    res = (double)PyLong_AsLongLong(c) / (double)PyLong_AsLongLong(a);
  }
  Py_XDECREF(c);
  Py_XDECREF(a);
  return res;
}

}  // extern "C"

extern "C" {

int ffc_model_save_checkpoint(ffc_model_t handle, const char *path) {
  // runtime/checkpoint.py save_checkpoint(path, ffmodel)
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *mod = PyImport_ImportModule("flexflow_tpu.runtime.checkpoint");
  if (!mod) { set_error_from_python(); return -1; }
  PyObject *res = PyObject_CallMethod(mod, "save_checkpoint", "sO", path,
                                      st->model);
  Py_DECREF(mod);
  if (!res) { set_error_from_python(); return -1; }
  Py_DECREF(res);
  return 0;
}

int ffc_model_restore_checkpoint(ffc_model_t handle, const char *path) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *mod = PyImport_ImportModule("flexflow_tpu.runtime.checkpoint");
  if (!mod) { set_error_from_python(); return -1; }
  PyObject *res = PyObject_CallMethod(mod, "restore_checkpoint", "sO", path,
                                      st->model);
  Py_DECREF(mod);
  if (!res) { set_error_from_python(); return -1; }
  Py_DECREF(res);
  return 0;
}

int ffc_model_export_strategy(ffc_model_t handle, const char *path) {
  // FFModel.export_strategy_file (the --export-strategy flow)
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *res = PyObject_CallMethod(st->model, "export_strategy_file", "s",
                                      path);
  if (!res) { set_error_from_python(); return -1; }
  Py_DECREF(res);
  return 0;
}

double ffc_model_eval(ffc_model_t handle, const float *x, const int32_t *y,
                      int64_t n, int64_t x_row_elems) {
  // returns eval accuracy in [0,1], or -1 on error
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *xa = np_from_buffer(x, n * x_row_elems, "float32", n, x_row_elems);
  if (!xa) return -1.0;
  xa = reshape_to_input_dims(st, xa, n);
  if (!xa) return -1.0;
  PyObject *ya = np_from_buffer(y, n, "int32", n, 1);
  if (!ya) { Py_DECREF(xa); return -1.0; }
  PyObject *args = PyTuple_Pack(2, xa, ya);
  PyObject *kwargs = Py_BuildValue("{s:O}", "verbose", Py_False);
  PyObject *metrics = call_method(st->model, "eval", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(xa);
  Py_DECREF(ya);
  if (!metrics) return -1.0;
  PyObject *c = PyObject_GetAttrString(metrics, "train_correct");
  PyObject *a = PyObject_GetAttrString(metrics, "train_all");
  double res = -1.0;
  if (c && a) {
    // train_correct may be a float (slot-averaged counts)
    PyObject *cf = PyNumber_Float(c);
    double all = (double)PyLong_AsLongLong(a);
    if (PyErr_Occurred() || !cf) {
      set_error_from_python();  // conversion failure, not a batch problem
    } else if (all > 0) {
      res = PyFloat_AsDouble(cf) / all;
    } else {
      g_error = "eval saw zero full batches (n < batch_size?)";
    }
    Py_XDECREF(cf);
  }
  Py_XDECREF(c);
  Py_XDECREF(a);
  Py_DECREF(metrics);
  return res;
}

int64_t ffc_model_fit_tokens(ffc_model_t handle, const int32_t *x,
                             const int32_t *y, int64_t n, int64_t seq,
                             int epochs) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *xa = np_from_buffer(x, n * seq, "int32", n, seq, true);
  if (!xa) return -1;
  PyObject *ya = np_from_buffer(y, n * seq, "int32", n, seq, true);
  if (!ya) { Py_DECREF(xa); return -1; }
  PyObject *args = PyTuple_Pack(2, xa, ya);
  PyObject *kwargs = Py_BuildValue("{s:i,s:O}", "epochs", epochs, "verbose",
                                   Py_False);
  PyObject *metrics = call_method(st->model, "fit", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(xa);
  Py_DECREF(ya);
  if (!metrics) return -1;
  Py_XDECREF(st->last_metrics);
  st->last_metrics = metrics;
  PyObject *ta = PyObject_GetAttrString(metrics, "train_all");
  int64_t out = ta ? PyLong_AsLongLong(ta) : -1;
  Py_XDECREF(ta);
  return out;
}

int64_t ffc_model_fit_dataloader(ffc_model_t handle, const float *x,
                                 const int32_t *y, int64_t n,
                                 int64_t x_row_elems, int epochs,
                                 int shuffle) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *xa = np_from_buffer(x, n * x_row_elems, "float32", n, x_row_elems);
  if (!xa) return -1;
  xa = reshape_to_input_dims(st, xa, n);
  if (!xa) return -1;
  PyObject *ya = np_from_buffer(y, n, "int32", n, 1);
  if (!ya) { Py_DECREF(xa); return -1; }
  PyObject *sh = shuffle ? Py_True : Py_False;
  PyObject *dlx_args = PyTuple_Pack(2, Py_None, xa);
  PyObject *dlx_kw = Py_BuildValue("{s:O}", "shuffle", sh);
  PyObject *dlx = call_method(st->model, "create_data_loader", dlx_args,
                              dlx_kw);
  Py_DECREF(dlx_args);
  Py_DECREF(dlx_kw);
  Py_DECREF(xa);
  if (!dlx) { Py_DECREF(ya); return -1; }
  // the label loader must shuffle in LOCKSTEP with the input loader:
  // same seed + shuffle flag (SingleDataLoader is seed-deterministic)
  PyObject *dly_args = PyTuple_Pack(2, Py_None, ya);
  PyObject *dly_kw = Py_BuildValue("{s:O}", "shuffle", sh);
  PyObject *dly = call_method(st->model, "create_data_loader", dly_args,
                              dly_kw);
  Py_DECREF(dly_args);
  Py_DECREF(dly_kw);
  Py_DECREF(ya);
  if (!dly) { Py_DECREF(dlx); return -1; }
  PyObject *loaders = PyList_New(2);
  PyList_SetItem(loaders, 0, dlx);  // steals refs
  PyList_SetItem(loaders, 1, dly);
  PyObject *args = PyTuple_New(0);
  PyObject *kwargs = Py_BuildValue("{s:O,s:i,s:O}", "dataloaders", loaders,
                                   "epochs", epochs, "verbose", Py_False);
  PyObject *metrics = call_method(st->model, "fit", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(loaders);
  if (!metrics) return -1;
  Py_XDECREF(st->last_metrics);
  st->last_metrics = metrics;
  PyObject *ta = PyObject_GetAttrString(metrics, "train_all");
  int64_t out = ta ? PyLong_AsLongLong(ta) : -1;
  Py_XDECREF(ta);
  return out;
}

int ffc_model_generate(ffc_model_t handle, const int32_t *prompt,
                       int64_t batch, int64_t prompt_len,
                       int max_new_tokens, int32_t *out) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *pa = np_from_buffer(prompt, batch * prompt_len, "int32", batch,
                                prompt_len, true);
  if (!pa) return -1;
  PyObject *args = PyTuple_Pack(1, pa);
  PyObject *kwargs = Py_BuildValue("{s:i}", "max_new_tokens", max_new_tokens);
  PyObject *toks = call_method(st->model, "generate", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(pa);
  if (!toks) return -1;
  PyObject *np = np_module();
  PyObject *flat = PyObject_CallMethod(
      np, "ascontiguousarray", "Os", toks, "int32");
  Py_DECREF(toks);
  if (!flat) { set_error_from_python(); return -1; }
  Py_buffer view;
  if (PyObject_GetBuffer(flat, &view, PyBUF_SIMPLE) != 0) {
    set_error_from_python();
    Py_DECREF(flat);
    return -1;
  }
  int64_t want = batch * max_new_tokens * (int64_t)sizeof(int32_t);
  if ((int64_t)view.len != want) {
    g_error = "generate returned an unexpected token-buffer size";
    PyBuffer_Release(&view);
    Py_DECREF(flat);
    return -1;
  }
  memcpy(out, view.buf, (size_t)want);
  PyBuffer_Release(&view);
  Py_DECREF(flat);
  return 0;
}

// ---- vision / structural / MoE ops + config knobs (round 4: the
// remaining reference C surface, python/flexflow_c.cc:181-1751) ----------

extern "C" {

ffc_tensor_t ffc_model_transpose(ffc_model_t handle, ffc_tensor_t input,
                                 int ndims, const int *perm) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *pl = PyList_New(ndims);
  for (int i = 0; i < ndims; i++) {
    PyList_SetItem(pl, i, PyLong_FromLong(perm[i]));
  }
  PyObject *args = PyTuple_Pack(2, reinterpret_cast<PyObject *>(input), pl);
  PyObject *t = call_method(st->model, "transpose", args);
  Py_DECREF(args);
  Py_DECREF(pl);
  return t;
}

ffc_tensor_t ffc_model_reshape(ffc_model_t handle, ffc_tensor_t input,
                               int ndims, const int64_t *dims) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *pl = PyList_New(ndims);
  for (int i = 0; i < ndims; i++) {
    PyList_SetItem(pl, i, PyLong_FromLongLong(dims[i]));
  }
  PyObject *args = PyTuple_Pack(2, reinterpret_cast<PyObject *>(input), pl);
  PyObject *t = call_method(st->model, "reshape", args);
  Py_DECREF(args);
  Py_DECREF(pl);
  return t;
}

ffc_tensor_t ffc_model_dropout(ffc_model_t handle, ffc_tensor_t input,
                               float rate) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *kwargs = Py_BuildValue("{s:f}", "rate", rate);
  PyObject *t = call_method(st->model, "dropout", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  return t;
}

ffc_tensor_t ffc_model_cast(ffc_model_t handle, ffc_tensor_t input,
                            ffc_dtype_t dtype) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *dt = enum_member("DataType", dt_name(dtype));
  if (!dt) return nullptr;
  PyObject *args = PyTuple_Pack(2, reinterpret_cast<PyObject *>(input), dt);
  PyObject *t = call_method(st->model, "cast", args);
  Py_DECREF(args);
  Py_DECREF(dt);
  return t;
}

ffc_tensor_t ffc_model_batch_norm(ffc_model_t handle, ffc_tensor_t input,
                                  int relu) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *kwargs = Py_BuildValue("{s:O}", "relu",
                                   relu ? Py_True : Py_False);
  PyObject *t = call_method(st->model, "batch_norm", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  return t;
}

static ffc_tensor_t binary2(ffc_model_t handle, ffc_tensor_t a,
                            ffc_tensor_t b, const char *name) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(2, reinterpret_cast<PyObject *>(a),
                                reinterpret_cast<PyObject *>(b));
  PyObject *t = call_method(st->model, name, args);
  Py_DECREF(args);
  return t;
}

ffc_tensor_t ffc_model_multiply(ffc_model_t m, ffc_tensor_t a,
                                ffc_tensor_t b) {
  return binary2(m, a, b, "multiply");
}
ffc_tensor_t ffc_model_subtract(ffc_model_t m, ffc_tensor_t a,
                                ffc_tensor_t b) {
  return binary2(m, a, b, "subtract");
}
ffc_tensor_t ffc_model_sigmoid(ffc_model_t m, ffc_tensor_t x) {
  return unary(m, x, "sigmoid");
}
ffc_tensor_t ffc_model_tanh(ffc_model_t m, ffc_tensor_t x) {
  return unary(m, x, "tanh");
}
ffc_tensor_t ffc_model_gelu(ffc_model_t m, ffc_tensor_t x) {
  return unary(m, x, "gelu");
}

// copy the elements of a Python list/tuple of tensors into `out`
// (new references); returns 0/-1
static int unpack_tensor_seq(PyObject *seq, int expected, ffc_tensor_t *out) {
  PyObject *fast = PySequence_Fast(seq, "expected a tensor sequence");
  if (!fast) { set_error_from_python(); return -1; }
  Py_ssize_t n = PySequence_Fast_GET_SIZE(fast);
  if (n != expected) {
    g_error = "unexpected number of output tensors";
    Py_DECREF(fast);
    return -1;
  }
  for (Py_ssize_t i = 0; i < n; i++) {
    PyObject *t = PySequence_Fast_GET_ITEM(fast, i);
    Py_INCREF(t);
    out[i] = t;
  }
  Py_DECREF(fast);
  return 0;
}

int ffc_model_split(ffc_model_t handle, ffc_tensor_t input, int n,
                    const int *sizes, int axis, ffc_tensor_t *out) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *sl = PyList_New(n);
  for (int i = 0; i < n; i++) {
    PyList_SetItem(sl, i, PyLong_FromLong(sizes[i]));
  }
  PyObject *args = PyTuple_Pack(2, reinterpret_cast<PyObject *>(input), sl);
  PyObject *kwargs = Py_BuildValue("{s:i}", "axis", axis);
  PyObject *parts = call_method(st->model, "split", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(sl);
  if (!parts) return -1;
  int rc = unpack_tensor_seq(parts, n, out);
  Py_DECREF(parts);
  return rc;
}

int ffc_model_top_k(ffc_model_t handle, ffc_tensor_t input, int k,
                    int sorted_, ffc_tensor_t *values,
                    ffc_tensor_t *indices) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *kwargs = Py_BuildValue("{s:i,s:O}", "k", k, "sorted",
                                   sorted_ ? Py_True : Py_False);
  PyObject *pair = call_method(st->model, "top_k", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  if (!pair) return -1;
  ffc_tensor_t out[2] = {nullptr, nullptr};
  int rc = unpack_tensor_seq(pair, 2, out);
  Py_DECREF(pair);
  if (rc == 0) {
    *values = out[0];
    *indices = out[1];
  }
  return rc;
}

int ffc_model_group_by(ffc_model_t handle, ffc_tensor_t input,
                       ffc_tensor_t assign, int n, float alpha,
                       ffc_tensor_t *out) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = Py_BuildValue("(OOif)",
                                 reinterpret_cast<PyObject *>(input),
                                 reinterpret_cast<PyObject *>(assign),
                                 n, alpha);
  if (!args) { set_error_from_python(); return -1; }
  PyObject *groups = call_method(st->model, "group_by", args);
  Py_DECREF(args);
  if (!groups) return -1;
  int rc = unpack_tensor_seq(groups, n, out);
  Py_DECREF(groups);
  return rc;
}

ffc_tensor_t ffc_model_aggregate(ffc_model_t handle, int n_inputs,
                                 const ffc_tensor_t *inputs, int n,
                                 float lambda_bal) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *lst = PyList_New(n_inputs);
  for (int i = 0; i < n_inputs; i++) {
    PyObject *t = reinterpret_cast<PyObject *>(inputs[i]);
    Py_INCREF(t);
    PyList_SetItem(lst, i, t);
  }
  PyObject *args = Py_BuildValue("(Oi)", lst, n);
  PyObject *kwargs = Py_BuildValue("{s:f}", "lambda_bal", lambda_bal);
  PyObject *t = call_method(st->model, "aggregate", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(lst);
  return t;
}

ffc_tensor_t ffc_model_moe(ffc_model_t handle, ffc_tensor_t input,
                           int num_exp, int num_select, int expert_hidden,
                           float alpha, float lambda_bal) {
  g_error.clear();
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *kwargs = Py_BuildValue(
      "{s:i,s:i,s:i,s:f,s:f}", "num_exp", num_exp, "num_select", num_select,
      "expert_hidden_size", expert_hidden, "alpha", alpha, "lambda_bal",
      lambda_bal);
  PyObject *t = call_method(st->model, "moe", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  return t;
}

// FFConfig is a plain dataclass: setattr on a misspelled field would
// silently create a NEW attribute and the knob would never take effect —
// reject unknown fields instead
static int config_set(ffc_config_t cfg, const char *field, PyObject *v) {
  PyObject *c = reinterpret_cast<PyObject *>(cfg);
  if (PyObject_HasAttrString(c, field) != 1) {
    g_error = std::string("FFConfig has no field '") + field + "'";
    Py_DECREF(v);
    return -1;
  }
  int rc = PyObject_SetAttrString(c, field, v);
  Py_DECREF(v);
  if (rc != 0) set_error_from_python();
  return rc;
}

int ffc_config_set_int(ffc_config_t cfg, const char *field, int64_t value) {
  g_error.clear();
  return config_set(cfg, field, PyLong_FromLongLong(value));
}

int ffc_config_set_str(ffc_config_t cfg, const char *field,
                       const char *value) {
  g_error.clear();
  return config_set(cfg, field, PyUnicode_FromString(value));
}

}  // extern "C" (vision/MoE/config additions)

}  // extern "C" (checkpoint/strategy/eval/transformer additions)

// ---- long-tail surface (reference python/flexflow_c.cc:181-1751): SGD,
// initializer objects, elementwise/scalar/reduction/gather/LSTM. These
// wrappers null-check their handles (the error-path contract the tests
// exercise: a NULL handle or input sets ffc_last_error instead of
// crashing).

namespace {

bool require(bool ok, const char *what) {
  if (!ok) g_error = std::string("null ") + what;
  return ok;
}

ffc_tensor_t unary_op(ffc_model_t handle, ffc_tensor_t x,
                      const char *method) {
  g_error.clear();
  if (!require(handle != nullptr, "model handle") ||
      !require(x != nullptr, "input tensor"))
    return nullptr;
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(x));
  PyObject *t = call_method(st->model, method, args);
  Py_DECREF(args);
  return t;
}

ffc_tensor_t binary_op(ffc_model_t handle, ffc_tensor_t a, ffc_tensor_t b,
                       const char *method) {
  g_error.clear();
  if (!require(handle != nullptr, "model handle") ||
      !require(a != nullptr && b != nullptr, "input tensor"))
    return nullptr;
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(2, reinterpret_cast<PyObject *>(a),
                                reinterpret_cast<PyObject *>(b));
  PyObject *t = call_method(st->model, method, args);
  Py_DECREF(args);
  return t;
}

ffc_tensor_t scalar_op(ffc_model_t handle, ffc_tensor_t x,
                       const char *method, float scalar) {
  g_error.clear();
  if (!require(handle != nullptr, "model handle") ||
      !require(x != nullptr, "input tensor"))
    return nullptr;
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = Py_BuildValue("(Of)",
                                 reinterpret_cast<PyObject *>(x), scalar);
  PyObject *t = call_method(st->model, method, args);
  Py_DECREF(args);
  return t;
}

ffc_initializer_t make_initializer(const char *cls, PyObject *kwargs) {
  g_error.clear();
  PyObject *mod = ff_module();
  if (!mod) { Py_XDECREF(kwargs); return nullptr; }
  PyObject *c = PyObject_GetAttrString(mod, cls);
  if (!c) { set_error_from_python(); Py_XDECREF(kwargs); return nullptr; }
  PyObject *args = PyTuple_New(0);
  PyObject *obj = PyObject_Call(c, args, kwargs);
  Py_DECREF(c);
  Py_DECREF(args);
  Py_XDECREF(kwargs);
  if (!obj) set_error_from_python();
  return obj;
}

}  // namespace

extern "C" {

int ffc_model_compile_sgd(ffc_model_t handle, ffc_loss_t loss, float lr,
                          float momentum, int nesterov,
                          float weight_decay) {
  g_error.clear();
  if (!require(handle != nullptr, "model handle")) return -1;
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *mod = ff_module();
  if (!mod) return -1;
  PyObject *opt_cls = PyObject_GetAttrString(mod, "SGDOptimizer");
  if (!opt_cls) { set_error_from_python(); return -1; }
  PyObject *okw = Py_BuildValue("{s:f,s:f,s:O,s:f}", "lr", lr, "momentum",
                                momentum, "nesterov",
                                nesterov ? Py_True : Py_False,
                                "weight_decay", weight_decay);
  PyObject *oargs = PyTuple_New(0);
  PyObject *opt = PyObject_Call(opt_cls, oargs, okw);
  Py_DECREF(opt_cls);
  Py_DECREF(oargs);
  Py_DECREF(okw);
  if (!opt) { set_error_from_python(); return -1; }
  return compile_with_optimizer(st, opt, loss);
}

ffc_initializer_t ffc_glorot_uniform_initializer_create(int seed) {
  return make_initializer("GlorotUniformInitializer",
                          Py_BuildValue("{s:i}", "seed", seed));
}

ffc_initializer_t ffc_zero_initializer_create(void) {
  return make_initializer("ZeroInitializer", nullptr);
}

ffc_initializer_t ffc_constant_initializer_create(float value) {
  return make_initializer("ConstantInitializer",
                          Py_BuildValue("{s:f}", "value", value));
}

ffc_initializer_t ffc_uniform_initializer_create(int seed, float minv,
                                                 float maxv) {
  return make_initializer(
      "UniformInitializer",
      Py_BuildValue("{s:f,s:f,s:i}", "minv", minv, "maxv", maxv, "seed",
                    seed));
}

ffc_initializer_t ffc_norm_initializer_create(int seed, float mean,
                                              float stddev) {
  return make_initializer(
      "NormInitializer",
      Py_BuildValue("{s:f,s:f,s:i}", "mean", mean, "stddev", stddev,
                    "seed", seed));
}

void ffc_initializer_destroy(ffc_initializer_t init) {
  Py_XDECREF(reinterpret_cast<PyObject *>(init));
}

ffc_tensor_t ffc_model_dense_init(ffc_model_t handle, ffc_tensor_t input,
                                  int out_dim, ffc_activation_t act,
                                  int use_bias,
                                  ffc_initializer_t kernel_init,
                                  ffc_initializer_t bias_init) {
  g_error.clear();
  if (!require(handle != nullptr, "model handle") ||
      !require(input != nullptr, "input tensor"))
    return nullptr;
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *act_obj = enum_member("ActiMode", act_name(act));
  if (!act_obj) return nullptr;
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *kwargs = Py_BuildValue(
      "{s:i,s:O,s:i,s:O,s:O}", "out_dim", out_dim, "activation", act_obj,
      "use_bias", use_bias ? 1 : 0, "kernel_initializer",
      kernel_init ? reinterpret_cast<PyObject *>(kernel_init) : Py_None,
      "bias_initializer",
      bias_init ? reinterpret_cast<PyObject *>(bias_init) : Py_None);
  PyObject *t = call_method(st->model, "dense", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  Py_DECREF(act_obj);
  return t;
}

ffc_tensor_t ffc_model_divide(ffc_model_t m, ffc_tensor_t a,
                              ffc_tensor_t b) {
  return binary_op(m, a, b, "divide");
}
ffc_tensor_t ffc_model_max(ffc_model_t m, ffc_tensor_t a, ffc_tensor_t b) {
  return binary_op(m, a, b, "max");
}
ffc_tensor_t ffc_model_min(ffc_model_t m, ffc_tensor_t a, ffc_tensor_t b) {
  return binary_op(m, a, b, "min");
}
ffc_tensor_t ffc_model_exp(ffc_model_t m, ffc_tensor_t x) {
  return unary_op(m, x, "exp");
}
ffc_tensor_t ffc_model_sin(ffc_model_t m, ffc_tensor_t x) {
  return unary_op(m, x, "sin");
}
ffc_tensor_t ffc_model_cos(ffc_model_t m, ffc_tensor_t x) {
  return unary_op(m, x, "cos");
}
ffc_tensor_t ffc_model_rsqrt(ffc_model_t m, ffc_tensor_t x) {
  return unary_op(m, x, "rsqrt");
}
ffc_tensor_t ffc_model_identity(ffc_model_t m, ffc_tensor_t x) {
  return unary_op(m, x, "identity");
}
ffc_tensor_t ffc_model_pow(ffc_model_t m, ffc_tensor_t x, float exponent) {
  return scalar_op(m, x, "pow", exponent);
}
ffc_tensor_t ffc_model_scalar_add(ffc_model_t m, ffc_tensor_t x,
                                  float scalar) {
  return scalar_op(m, x, "scalar_add", scalar);
}
ffc_tensor_t ffc_model_scalar_sub(ffc_model_t m, ffc_tensor_t x,
                                  float scalar) {
  return scalar_op(m, x, "scalar_sub", scalar);
}
ffc_tensor_t ffc_model_scalar_multiply(ffc_model_t m, ffc_tensor_t x,
                                       float scalar) {
  return scalar_op(m, x, "scalar_multiply", scalar);
}
ffc_tensor_t ffc_model_scalar_true_divide(ffc_model_t m, ffc_tensor_t x,
                                          float scalar) {
  return scalar_op(m, x, "scalar_true_divide", scalar);
}

ffc_tensor_t ffc_model_reverse(ffc_model_t handle, ffc_tensor_t x,
                               int axis) {
  g_error.clear();
  if (!require(handle != nullptr, "model handle") ||
      !require(x != nullptr, "input tensor"))
    return nullptr;
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = Py_BuildValue("(Oi)",
                                 reinterpret_cast<PyObject *>(x), axis);
  PyObject *t = call_method(st->model, "reverse", args);
  Py_DECREF(args);
  return t;
}

ffc_tensor_t ffc_model_gather(ffc_model_t handle, ffc_tensor_t input,
                              ffc_tensor_t index, int axis) {
  g_error.clear();
  if (!require(handle != nullptr, "model handle") ||
      !require(input != nullptr && index != nullptr, "input tensor"))
    return nullptr;
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = Py_BuildValue("(OOi)",
                                 reinterpret_cast<PyObject *>(input),
                                 reinterpret_cast<PyObject *>(index), axis);
  PyObject *t = call_method(st->model, "gather", args);
  Py_DECREF(args);
  return t;
}

static ffc_tensor_t reduce_op(ffc_model_t handle, ffc_tensor_t input,
                              const int *axes, int n_axes, int keepdims,
                              const char *method) {
  g_error.clear();
  if (!require(handle != nullptr, "model handle") ||
      !require(input != nullptr, "input tensor") ||
      !require(axes != nullptr && n_axes > 0, "reduction axes"))
    return nullptr;
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *ax = PyTuple_New(n_axes);
  for (int i = 0; i < n_axes; i++)
    PyTuple_SetItem(ax, i, PyLong_FromLong(axes[i]));
  PyObject *args = Py_BuildValue("(ONO)",
                                 reinterpret_cast<PyObject *>(input), ax,
                                 keepdims ? Py_True : Py_False);
  PyObject *t = call_method(st->model, method, args);
  Py_DECREF(args);
  return t;
}

ffc_tensor_t ffc_model_reduce_sum(ffc_model_t m, ffc_tensor_t input,
                                  const int *axes, int n_axes,
                                  int keepdims) {
  return reduce_op(m, input, axes, n_axes, keepdims, "reduce_sum");
}

ffc_tensor_t ffc_model_mean(ffc_model_t m, ffc_tensor_t input,
                            const int *axes, int n_axes, int keepdims) {
  return reduce_op(m, input, axes, n_axes, keepdims, "mean");
}

int ffc_model_lstm(ffc_model_t handle, ffc_tensor_t input, int hidden,
                   int use_bias, ffc_tensor_t out[3]) {
  g_error.clear();
  if (!require(handle != nullptr, "model handle") ||
      !require(input != nullptr, "input tensor") ||
      !require(out != nullptr, "output array"))
    return -1;
  auto *st = reinterpret_cast<ModelState *>(handle);
  PyObject *args = PyTuple_Pack(1, reinterpret_cast<PyObject *>(input));
  PyObject *kwargs = Py_BuildValue("{s:i,s:i}", "hidden", hidden,
                                   "use_bias", use_bias ? 1 : 0);
  PyObject *tup = call_method(st->model, "lstm", args, kwargs);
  Py_DECREF(args);
  Py_DECREF(kwargs);
  if (!tup) return -1;
  if (!PyTuple_Check(tup) || PyTuple_Size(tup) != 3) {
    g_error = "lstm did not return (outputs, h_n, c_n)";
    Py_DECREF(tup);
    return -1;
  }
  for (int i = 0; i < 3; i++) {
    PyObject *t = PyTuple_GetItem(tup, i);
    Py_INCREF(t);
    out[i] = t;
  }
  Py_DECREF(tup);
  return 0;
}

}  // extern "C" (long-tail additions)
