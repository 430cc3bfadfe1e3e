// C ABI over the paddle_tpu inference Predictor.
//
// Reference: fluid/inference/capi/paddle_c_api.h (PD_NewAnalysisConfig,
// PD_NewPredictor :279, PD_PredictorRun :124, PD_DeletePredictor :282) —
// the surface go/paddle/predictor.go binds to.  There the C API fronts
// the C++ AnalysisPredictor; here the serving engine is the XLA AOT
// executable driven by the Python Predictor, so the C ABI EMBEDS CPython
// (Py_InitializeEx when standalone; GIL-acquire when the host process
// already runs an interpreter, which is how the test suite exercises it).
// Float32 tensors only in v1 — the dominant serving dtype; extend the
// dtype switch as needed.
//
// Build:  g++ -shared -fPIC predictor_capi.cpp -o libpaddle_tpu_capi.so \
//             -I$(python -c "import sysconfig;print(sysconfig.get_path('include'))") \
//             -lpython3.12
#include <Python.h>

#include <cstdint>
#include <cstring>
#include <string>
#include <vector>

// the public contract lives in the header (consumed by go/paddle and C
// clients); this TU provides PT_Predictor's definition
#include "paddle_tpu_capi.h"

extern "C" {

struct PT_Predictor {
  PyObject* predictor;  // paddle_tpu.inference.Predictor
};

static int g_we_initialized = 0;

static int ensure_python() {
  if (!Py_IsInitialized()) {
    Py_InitializeEx(0);
    g_we_initialized = 1;
    // release the GIL the init thread holds: every entry point uses
    // PyGILState_Ensure/Release, and a second host thread would
    // otherwise deadlock in Ensure while this thread never re-enters
    PyEval_SaveThread();
  }
  return 1;
}

// Returns NULL on failure; error text (if any) is printed to stderr.
PT_Predictor* PT_NewPredictor(const char* model_path_prefix) {
  ensure_python();
  PyGILState_STATE g = PyGILState_Ensure();
  PT_Predictor* out = nullptr;
  PyObject *mod = nullptr, *cfg_cls = nullptr, *cfg = nullptr,
           *create = nullptr, *pred = nullptr;
  mod = PyImport_ImportModule("paddle_tpu.inference");
  if (!mod) goto fail;
  cfg_cls = PyObject_GetAttrString(mod, "Config");
  if (!cfg_cls) goto fail;
  cfg = PyObject_CallFunction(cfg_cls, "s", model_path_prefix);
  if (!cfg) goto fail;
  create = PyObject_GetAttrString(mod, "create_predictor");
  if (!create) goto fail;
  pred = PyObject_CallFunctionObjArgs(create, cfg, nullptr);
  if (!pred) goto fail;
  out = new PT_Predictor{pred};
  goto done;
fail:
  PyErr_Print();
done:
  Py_XDECREF(create);
  Py_XDECREF(cfg);
  Py_XDECREF(cfg_cls);
  Py_XDECREF(mod);
  PyGILState_Release(g);
  return out;
}

// inputs: n_inputs float32 buffers with shapes[i] of ndims[i] dims.
// Returns number of outputs (<0 on error); outputs returned via
// PT_GetOutput after a successful run.
int32_t PT_PredictorRun(PT_Predictor* p, const float* const* inputs,
                        const int64_t* const* shapes,
                        const int32_t* ndims, int32_t n_inputs) {
  if (!p || !p->predictor) return -1;
  PyGILState_STATE g = PyGILState_Ensure();
  int32_t rc = -1;
  PyObject *np = nullptr, *feed = nullptr, *outs = nullptr,
           *run = nullptr, *frombuf = nullptr;
  np = PyImport_ImportModule("numpy");
  if (!np) goto fail;
  feed = PyList_New(n_inputs);
  if (!feed) goto fail;
  for (int32_t i = 0; i < n_inputs; ++i) {
    int64_t numel = 1;
    for (int32_t d = 0; d < ndims[i]; ++d) numel *= shapes[i][d];
    // numpy.frombuffer(bytes, float32).reshape(shape).copy()
    PyObject* bytes = PyBytes_FromStringAndSize(
        reinterpret_cast<const char*>(inputs[i]),
        static_cast<Py_ssize_t>(numel * sizeof(float)));
    if (!bytes) goto fail;
    PyObject* arr = PyObject_CallMethod(np, "frombuffer", "Os", bytes,
                                        "float32");
    Py_DECREF(bytes);
    if (!arr) goto fail;
    PyObject* shape = PyTuple_New(ndims[i]);
    for (int32_t d = 0; d < ndims[i]; ++d)
      PyTuple_SET_ITEM(shape, d, PyLong_FromLongLong(shapes[i][d]));
    PyObject* reshaped = PyObject_CallMethod(arr, "reshape", "O", shape);
    Py_DECREF(shape);
    Py_DECREF(arr);
    if (!reshaped) goto fail;
    PyList_SET_ITEM(feed, i, reshaped);  // steals
  }
  outs = PyObject_CallMethod(p->predictor, "run", "O", feed);
  if (!outs) goto fail;
  // stash outputs on the predictor wrapper for PT_GetOutput
  if (PyObject_SetAttrString(p->predictor, "_capi_outputs", outs) < 0)
    goto fail;
  rc = static_cast<int32_t>(PySequence_Size(outs));
  goto done;
fail:
  PyErr_Print();
done:
  Py_XDECREF(outs);
  Py_XDECREF(feed);
  Py_XDECREF(np);
  Py_XDECREF(run);
  Py_XDECREF(frombuf);
  PyGILState_Release(g);
  return rc;
}

// Copy output idx into caller-managed PT_Output (free with PT_FreeOutput).
int32_t PT_GetOutput(PT_Predictor* p, int32_t idx, PT_Output* out) {
  if (!p || !p->predictor || !out) return -1;
  PyGILState_STATE g = PyGILState_Ensure();
  int32_t rc = -1;
  PyObject *outs = nullptr, *np = nullptr, *item = nullptr,
           *arr = nullptr, *ravel = nullptr, *bytes = nullptr;
  outs = PyObject_GetAttrString(p->predictor, "_capi_outputs");
  if (!outs) goto fail;
  item = PySequence_GetItem(outs, idx);
  if (!item) goto fail;
  np = PyImport_ImportModule("numpy");
  if (!np) goto fail;
  arr = PyObject_CallMethod(np, "ascontiguousarray", "O", item);
  if (!arr) goto fail;
  {
    PyObject* f32 = PyObject_CallMethod(arr, "astype", "s", "float32");
    if (!f32) goto fail;
    Py_DECREF(arr);
    arr = f32;
  }
  {
    PyObject* shape = PyObject_GetAttrString(arr, "shape");
    if (!shape) goto fail;
    Py_ssize_t nd = PyTuple_Size(shape);
    out->ndim = static_cast<int32_t>(nd);
    out->shape = new int64_t[nd > 0 ? nd : 1];
    out->numel = 1;
    for (Py_ssize_t d = 0; d < nd; ++d) {
      out->shape[d] = PyLong_AsLongLong(PyTuple_GET_ITEM(shape, d));
      out->numel *= out->shape[d];
    }
    Py_DECREF(shape);
  }
  bytes = PyObject_CallMethod(arr, "tobytes", nullptr);
  if (!bytes) goto fail;
  {
    char* src = nullptr;
    Py_ssize_t len = 0;
    PyBytes_AsStringAndSize(bytes, &src, &len);
    out->data = new float[len / sizeof(float)];
    std::memcpy(out->data, src, static_cast<size_t>(len));
  }
  rc = 0;
  goto done;
fail:
  PyErr_Print();
done:
  Py_XDECREF(bytes);
  Py_XDECREF(arr);
  Py_XDECREF(item);
  Py_XDECREF(np);
  Py_XDECREF(outs);
  PyGILState_Release(g);
  return rc;
}

void PT_FreeOutput(PT_Output* out) {
  if (!out) return;
  delete[] out->data;
  delete[] out->shape;
  out->data = nullptr;
  out->shape = nullptr;
}

void PT_DeletePredictor(PT_Predictor* p) {
  if (!p) return;
  PyGILState_STATE g = PyGILState_Ensure();
  Py_XDECREF(p->predictor);
  PyGILState_Release(g);
  delete p;
}

}  // extern "C"
